"""Load modules, one per kind of traffic; a traffic file names its
module in its "load" key."""

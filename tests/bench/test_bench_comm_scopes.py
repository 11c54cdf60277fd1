"""The sharded engine's collectives are named in the program (`comm.*`
scopes inside `amtl.prox`), stay in the prox phase of `bench.phases`, and
the `amtl.run` span counts the bytes they move."""
import json
import os
import subprocess
import sys

from bench import phases, spec
from bench.loads.engine_loop import events_per_call

LOWER = r"""
import json, re, sys
sys.path.insert(0, sys.argv[1] + "/src")
import jax, jax.numpy as jnp
from repro.core import MTLProblem, make_engine
from repro.core.amtl import AMTLConfig, _run_events
from repro.launch.mesh import make_task_mesh

mesh = make_task_mesh(2)
t, n, d = 8, 6, 5
prob = MTLProblem(jnp.ones((t, n, d)), jnp.ones((t, n)), "lstsq", "nuclear",
                  0.1, jnp.full((t,), 4, jnp.int32))
out = {}
for mode in ("distributed", "replicated"):
    cfg = AMTLConfig(eta=0.1, eta_k=0.5, tau=2, engine="sharded",
                     event_batch=4, prox_every=4, prox_rank=2,
                     prox_mode=mode, batch_size=2)
    state = make_engine(prob, cfg, mesh).init(jnp.zeros((d, t)),
                                              jax.random.PRNGKey(0))
    text = _run_events.lower(prob, cfg, state, jnp.zeros((t,)), 8,
                             mesh).compile().as_text()
    ops = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \S+ ([\w\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and name and "/comm." in name.group(1):
            ops.append([m.group(1), m.group(2), name.group(1)])
    out[mode] = ops
print(json.dumps(out))
"""


def test_collectives_carry_comm_scopes_inside_the_prox_phase():
    """A CPU compile of the sharded step on a two-device mesh: each
    collective's op metadata names its `comm.*` scope, and the phase
    reading still puts it in `amtl.prox`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run([sys.executable, "-c", LOWER, str(spec.ROOT)],
                          cwd=spec.ROOT, env=env, capture_output=True,
                          text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    want = {"distributed": {"comm.sketch_psum": "all-reduce",
                            "comm.core_gather": "all-gather"},
            "replicated": {"comm.iterate_gather": "all-gather"}}
    for mode, scopes in want.items():
        ops = out[mode]
        for scope, opcode in scopes.items():
            assert any(code == opcode and f"/{scope}/" in path
                       for _, code, path in ops), (mode, scope, ops)
        for _, _, path in ops:
            assert phases.scope_of(path) == "amtl.prox", path
        assert {p.split("/comm.")[1].split("/")[0] for *_, p in ops} == {
            s.removeprefix("comm.") for s in scopes}


def test_run_span_counts_the_bytes_of_a_call_at_the_cell_shape():
    """One epoch call of the four-site cell: 3392 events, a refresh every
    32, each moving the (784, 24) psum'd sketch and the (24, 3400)
    gathered core in f32 (the span's `comm_bytes` is the call's refreshes
    times `refresh_comm_bytes`)."""
    from repro.core.amtl import AMTLConfig, refresh_comm_bytes

    cell = spec.cell("emnist62_writers.learn_4chip")
    cfg = cell.config
    k = events_per_call(cell)
    solver = AMTLConfig(eta=cfg["eta"], eta_k=0.5, tau=cfg["tau"],
                        engine=cfg["engine"], event_batch=cfg["event_batch"],
                        prox_every=cfg["prox_every"],
                        prox_rank=cfg["prox_rank"],
                        prox_mode=cfg["prox_mode"])
    per_refresh = refresh_comm_bytes(solver, cfg["dim"], cfg["num_tasks"],
                                     cfg["shards"])
    assert k == 3392 and k // solver.prox_every == 106
    assert per_refresh == (784 * 24 + 24 * 3400) * 4 == 401_664
    assert k // solver.prox_every * per_refresh == 106 * 401_664
    repl = refresh_comm_bytes(solver._replace(prox_mode="replicated"), 784,
                              3400, 4)
    assert repl == 784 * 3400 * 4
    batch = solver._replace(engine="batch", prox_mode="replicated")
    assert refresh_comm_bytes(batch, 784, 3400, None) == 0
    assert refresh_comm_bytes(solver, 784, 3400, 1) == 0


def test_collective_frac_reads_the_collectives_of_every_chip():
    """Collective device time over the chips' summed busy time, by the
    names the TPU compiler gives the sharded step's collectives; nothing
    to read where a trace has none."""
    from types import SimpleNamespace

    from bench import trace

    read = spec.metric_reader("collective_frac.sharded")
    ops = [trace.Event(f"/device:TPU:{c}", trace.OPS_LINE, name, 0.0, ns)
           for c in range(4)
           for name, ns in (("psum.11", 3e3), ("all-gather.8", 2e3),
                            ("all-reduce-done.2", 1e3), ("copy.247", 5e3),
                            ("lstsq_grad_sampled.4", 89e3))]
    busy = {f"/device:TPU:{c}": 100e-6 for c in range(4)}
    reduced = trace.Reduced(1e-3, 100e-6, busy, {}, [], ops)
    assert abs(read(SimpleNamespace(trace=reduced)) - 0.06) < 1e-12
    alone = reduced._replace(ops=[e for e in ops
                                  if e.name.startswith(("copy", "lstsq"))])
    assert read(SimpleNamespace(trace=alone)) is None
    assert read(SimpleNamespace()) is None

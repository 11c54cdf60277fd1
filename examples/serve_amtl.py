"""Learning-while-serving: an AMTL session behind a prediction API.

    PYTHONPATH=src python examples/serve_amtl.py

Streams request batches through an `AMTLServer`: every batch is scored
off the committed serving snapshot (predictions never wait on a
learning chunk), labeled feedback is coalesced into engine chunks under
per-task QoS caps, and the session checkpoints on a rotating
`keep_last` window.  Midway, the server "crashes" and is resumed from
the newest rotated checkpoint — the restart is bitwise invisible to
every subsequent prediction, which is the serving platform's core
contract (see `repro.serve`).  A final part moves the chunk loop onto
the background learner thread (PR 8) with a latency SLO: predictions
flow from the main thread while the learner absorbs feedback
concurrently, and after the drain the session state is still bitwise
ONE plain `engine.run` over the coalesced chunk log.

The closing chaos part (PR 10) replays a session under a scripted
`FaultPlan` driving all four injected fault types — NaN feedback
rejected at admission, a learner-thread crash healed by the supervisor,
a poisoned iterate quarantined with its folded rows rolled back, and a
crash between the store and engine checkpoint writes bridged by
`resume` — with the served snapshot finite throughout.
"""
import os
import tempfile

import jax
import numpy as np

from repro.core import AMTLConfig
from repro.data import make_mtl_problem
from repro.serve import AMTLServer, FaultPlan, InjectedFault, ServeConfig

BATCHES = 12
REQUESTS = 16          # prediction rows per request batch
FEEDBACK = 5           # labeled feedback rows per request batch


def _traffic(problem, seed=0):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, problem.num_tasks, size=(BATCHES, REQUESTS))
    x = rng.standard_normal((BATCHES, REQUESTS, problem.dim)) \
        .astype(np.float32)
    fb = rng.integers(0, problem.num_tasks, size=(BATCHES, FEEDBACK))
    return t, x, fb


def main():
    problem = make_mtl_problem(num_tasks=6, samples=40, dim=32, rank=2,
                               lam=0.1, seed=0)
    cfg = AMTLConfig(eta=1.0 / problem.lipschitz(), eta_k=0.9, tau=4,
                     engine="batch", prox_every=4, prox_rank=4)
    w0 = jax.numpy.zeros((problem.dim, problem.num_tasks), jax.numpy.float32)
    key = jax.random.PRNGKey(0)
    t, x, fb = _traffic(problem)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        serve_cfg = ServeConfig(chunk_events=16, task_chunk_quota=4,
                                max_pending_per_task=16,
                                ckpt_dir=ckpt_dir, checkpoint_every=10,
                                keep_last=3, max_batch=REQUESTS)
        # the reference server runs uninterrupted; the "production" one
        # will crash mid-stream and resume from its rotated checkpoints
        ref = AMTLServer(problem, cfg, w0, key,
                         serve_cfg._replace(ckpt_dir=None,
                                            checkpoint_every=None))
        server = AMTLServer(problem, cfg, w0, key, serve_cfg)

        for i in range(BATCHES // 2):
            preds, receipt, ran = server.serve(t[i], x[i], fb[i])
            ref.serve(t[i], x[i], fb[i])
            print(f"[serve] batch {i}: {preds.shape[0]} preds, "
                  f"{receipt.accepted} feedback accepted, "
                  f"{ran} events learned")
        # drain the queue on both (identical) servers, then flush a final
        # checkpoint: pending feedback is the one thing a crash loses, so
        # the demo crashes with an empty queue to keep the replay bitwise
        while server.pending_feedback:
            server.step()
            ref.step()
        server.checkpoint()
        records = sorted(os.listdir(ckpt_dir))
        print(f"[ckpt ] rotated window (keep_last=3): {records}")
        assert len(records) <= 3

        # -- crash + restart: resume from the newest rotated record ----
        del server
        server = AMTLServer.resume(problem, cfg, w0, key, serve_cfg)
        print(f"[boot ] resumed at event {server.event_count} "
              f"(pending feedback is the one thing a crash loses; "
              f"clients re-submit)")

        for i in range(BATCHES // 2, BATCHES):
            preds, _, _ = server.serve(t[i], x[i], fb[i])
            ref_preds, _, _ = ref.serve(t[i], x[i], fb[i])
            assert np.array_equal(np.asarray(preds), np.asarray(ref_preds)), \
                "restart must be bitwise invisible to predictions"
        print(f"[serve] batches {BATCHES // 2}..{BATCHES - 1}: resumed "
              "predictions bitwise == uninterrupted server")

        stats = server.stats()
        print(f"[stats] {stats}")
        assert stats["events"] == ref.stats()["events"]

        # -- threaded serving: the learner thread owns the chunk loop --
        from repro.core import make_engine
        start_event = server.event_count
        chunks_before = len(server.chunk_log)
        learner = server.start_learner()
        for i in range(BATCHES):
            server.predict(t[i % BATCHES], x[i % BATCHES])
            server.submit_feedback(fb[i % BATCHES])
        server.stop_learner(drain=True)   # finish every runnable chunk
        new_chunks = server.chunk_log[chunks_before:]
        print(f"[thread] learner absorbed {learner.events} events in "
              f"{learner.chunks} chunks while the main thread served")
        # replay law: the threaded session (including everything learned
        # before the crash) is bitwise ONE plain run over every event —
        # chunks compose bitwise at any boundary, threaded or not
        assert server.event_count == start_event + sum(new_chunks)
        eng = make_engine(problem, cfg)
        state = eng.run(eng.init(w0, key), None, server.event_count)
        assert np.array_equal(np.asarray(server.iterate()),
                              np.asarray(eng.iterate(state))), \
            "threaded serving must replay the chunk log bitwise"

    _chaos_part(problem, cfg, w0, key, t, x)
    print("OK: learning-while-serving with QoS, rotating checkpoints, a "
          "restart-transparent resume, a concurrent learner thread, and "
          "scripted-fault recovery (restart, quarantine, torn checkpoint).")


def _chaos_part(problem, cfg, w0, key, t, x):
    """Drive all four injected fault types against one supervised
    session and show every recovery contract holding."""
    import time

    rng = np.random.default_rng(1)

    def rows(k, seed):
        r = np.random.default_rng(seed)
        return (r.integers(0, problem.num_tasks, size=k),
                (r.standard_normal((k, problem.dim))
                 / np.sqrt(problem.dim)).astype(np.float32),
                r.standard_normal(k).astype(np.float32))

    with tempfile.TemporaryDirectory() as ckpt_dir:
        plan = FaultPlan(nan_feedback=[(0, 2)],      # labeled call 0, row 2
                         crash_on_chunks={1},        # learner dies, heals
                         poison_iterate_on_chunks={3},   # quarantined
                         fail_checkpoint_calls={1})  # store/engine split
        serve_cfg = ServeConfig(chunk_events=4, ckpt_dir=ckpt_dir,
                                restart_limit=2, restart_backoff_s=0.01)
        server = AMTLServer(problem, cfg, w0, key, serve_cfg,
                            fault_plan=plan)

        # 1) non-finite feedback dies at admission, not in the kernel
        receipt = server.submit_feedback(*rows(4, 0))
        print(f"[chaos] NaN feedback: {receipt.accepted} accepted, "
              f"{receipt.rejected} rejected (reason={receipt.reason})")
        assert receipt.reason == "nonfinite"

        # 2+3) supervised learner: scripted crash healed under backoff,
        # scripted iterate poison quarantined (folded rows rolled back)
        server.start_learner()
        for i in range(10):
            server.predict(t[i % len(t)], x[i % len(x)])
            server.submit_feedback(
                rng.integers(0, problem.num_tasks, size=4))
        deadline = time.perf_counter() + 60.0
        while time.perf_counter() < deadline:
            health = server.stats()["health"]
            if (health["learner_restarts"] >= 1
                    and health["nonfinite_chunks"] >= 1):
                break
            time.sleep(0.01)
        server.stop_learner(drain=True)
        health = server.stats()["health"]
        print(f"[chaos] crash healed: restarts={health['learner_restarts']}"
              f" recovery_ms={[round(ms, 1) for ms in health['recovery_ms']]}"
              f" | quarantined={health['quarantined_feedback']} events "
              f"across {health['nonfinite_chunks']} poisoned chunk(s)")
        assert health["learner_restarts"] >= 1
        assert health["nonfinite_chunks"] >= 1
        assert np.isfinite(np.asarray(server.iterate())).all(), \
            "the served snapshot must never go non-finite"

        # 4) checkpoint crash-split: the scripted kill lands between the
        # store write and the engine write; resume bridges the tear
        server.checkpoint()                    # call 0: whole record pair
        server.submit_feedback(rng.integers(0, problem.num_tasks, size=4))
        server.step()
        try:
            server.checkpoint()                # call 1: torn mid-pair
        except InjectedFault:
            print("[chaos] checkpoint torn between store and engine "
                  "writes (scripted)")
        resumed = AMTLServer.resume(problem, cfg, w0, key, serve_cfg)
        print(f"[chaos] resumed at event {resumed.event_count} from the "
              f"surviving record pair")
        assert resumed.event_count > 0
        assert np.isfinite(np.asarray(resumed.iterate())).all()


if __name__ == "__main__":
    main()

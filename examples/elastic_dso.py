"""Elastic DSO: the repo's first end-to-end kill-restore-reshard scenario.

A ``runtime.Supervisor`` drives the real distributed driver (``ShardedDSO``
on an 8-device host mesh) through a seeded fault plan:

  phase 1  crashes every ``--fault-every`` epochs; every crash restores the
           latest on-disk snapshot and re-runs the lost epochs.  The final
           iterate is compared against an uninterrupted run — max |delta|
           must be exactly 0.0 (deterministic resume).
  phase 2  continues the SAME store after a simulated cluster resize: a
           live reshard p=8 -> p'=4 mid-run, plus one more crash at the
           new size, finishing with the duality gap still shrinking.

``--chaos`` runs the self-healing gauntlet instead: a seeded plan with a
NaN injection, two crashes off the checkpoint boundaries, a bit-flipped
latest snapshot, and a persistent straggler.  The run must finish, land
within 1e-3 of the fault-free objective, and leave a recovery ledger
(written as JSON, ``--ledger-out``) recording every detection and action:
the NaN rollback, the quarantine + older-snapshot restore, and the
wall-clock replanning escalation (lpt schedule, then live reshard).

The chaos run also drives a ``repro.obs.RunRecorder``: every throughput
sample, snapshot/restore/reshard span, and ledger event lands in ONE
ordered JSONL run-event log (``--events-out``) — the CI chaos artifact.

``--async-writes`` runs the same scenarios with
``SnapshotStore(async_writes=True)``: the npz serialization + atomic
rename drain on a background writer thread while the epoch loop keeps
running.  Every assertion is unchanged — crash recovery must still be
bit-identical and the corrupt snapshot must still be quarantined — which
is exactly the point: the Supervisor's flush-before-read barriers make
async writes invisible to recovery semantics.

    PYTHONPATH=src python examples/elastic_dso.py [--epochs N]
        [--fault-every K] [--ckpt-every K] [--async-writes]
        [--chaos [--ledger-out F] [--events-out F]]
"""

import argparse
import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "src"))
# 8 host devices BEFORE jax initializes — the mesh is a real 8-way shard_map
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

from repro.core.dso_dist import ShardedDSO, make_dso_mesh  # noqa: E402
from repro.data.synthetic import make_classification  # noqa: E402
from repro.obs import RunRecorder  # noqa: E402
from repro.runtime import (FaultEvent, SnapshotStore, Supervisor,  # noqa: E402
                           ledger_counts, periodic_crashes,
                           render_ledger_event)


def run_chaos(args):
    """The self-healing gauntlet: every fault class in one seeded run."""
    prob = make_classification(m=128, d=96, density=0.1, loss="hinge",
                               lam=1e-3, seed=0)
    # enough epochs that both trajectories are well-converged — the lpt /
    # reshard replan legitimately changes the schedule, so the two runs only
    # agree to 1e-3 once the objective has flattened out
    epochs = max(args.epochs, 32)
    ref = ShardedDSO(prob, make_dso_mesh(8), impl="auto", schedule="cyclic",
                     seed=5)
    ref.run_epochs(epochs, args.eta0)
    ref_primal = ref.metrics()["primal"]
    print(f"m={prob.m} d={prob.d}; chaos over {epochs} epochs, fault-free "
          f"primal {ref_primal:.6f}")

    # ckpt_every=2, so crashes at 3/5 are OFF checkpoint boundaries (lost
    # epochs re-run), the NaN lands right after the epoch-2 save, the
    # latest snapshot is bit-flipped at 6, and a persistent straggler
    # appears at 10 — late enough that warm clean chunks have set the
    # wall-clock baseline
    plan = (FaultEvent(2, "nan", 1), FaultEvent(3, "crash"),
            FaultEvent(5, "crash"), FaultEvent(6, "corrupt"),
            FaultEvent(7, "crash"), FaultEvent(10, "slow", 2))
    rec = RunRecorder(args.events_out,
                      meta=dict(run="elastic_dso_chaos", m=prob.m, d=prob.d,
                                epochs=epochs, eta0=args.eta0,
                                fault_plan=[ev.describe() for ev in plan]))
    with tempfile.TemporaryDirectory() as ckpt_dir:
        sup = Supervisor(SnapshotStore(ckpt_dir,
                                       async_writes=args.async_writes),
                         checkpoint_every=2,
                         eta0=args.eta0, fault_plan=plan,
                         straggler_delay_s=0.05, replan=True,
                         straggler_factor=1.5, straggler_patience=1,
                         reshard_to=4, obs=rec)
        opt, ledger = sup.run_sharded(prob, epochs, mesh=make_dso_mesh(8),
                                      impl="auto", schedule="cyclic",
                                      seed=5)
        for ev in ledger:
            print(f"  [ledger] {render_ledger_event(ev)}")
        counts = ledger_counts(ledger)
        primal = opt.metrics()["primal"]
        gap = abs(primal - ref_primal)
        done, p_final = opt.epochs_done, opt.p
        print(f"chaos: {counts}; final primal {primal:.6f} "
              f"(|delta| vs fault-free = {gap:.2e}); p={p_final}, "
              f"epochs={done}")

        # steady-state epoch wall: the replanning escalation shed the
        # straggler, so the post-replan solver's warm per-epoch time must
        # sit near the fault-free one (an un-replanned run would pay the
        # straggler delay on every epoch, forever)
        def s_per_epoch(o, n=4):
            o.run_epochs(n, args.eta0)
            o.wait()            # warm the chunk length (jit trace)
            t0 = time.perf_counter()
            o.run_epochs(n, args.eta0)
            o.wait()
            return (time.perf_counter() - t0) / n

        ff = s_per_epoch(ref)
        pr = s_per_epoch(opt)
        print(f"steady-state s/epoch: fault-free {ff:.4f}, post-replan "
              f"{pr:.4f} (ratio {pr / ff:.2f}; an un-replanned straggler "
              f"would pay {ff + 0.05:.4f} per epoch forever)")
        out = dict(counts=counts, primal=primal, ref_primal=ref_primal,
                   primal_gap=gap, quarantined=sup.store.quarantined,
                   fault_free_s_per_epoch=ff, post_replan_s_per_epoch=pr,
                   no_replan_s_per_epoch=ff + 0.05,
                   events=[ev.to_dict() for ev in ledger])
        with open(args.ledger_out, "w") as f:
            json.dump(out, f, indent=2, default=str)
        print(f"recovery ledger -> {args.ledger_out}")
        rec.close()
        print(f"run-event log -> {args.events_out} "
              f"({len(rec.events)} events)")
        # every fault class detected/acted on, and the run still converged
        assert counts.get("health", 0) >= 1, "NaN never detected"
        assert sup.store.quarantined, "corrupt snapshot never quarantined"
        assert counts.get("crash", 0) >= 2
        assert counts.get("straggler_replan", 0) >= 1, "no replanning"
        assert done == epochs
        assert gap <= 1e-3, f"objective {gap:.2e} off the fault-free run"
    print("CHAOS_OK")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--fault-every", type=int, default=3,
                    help="crash every K epochs in phase 1 (3 with the "
                         "default --ckpt-every 2 puts crashes off the "
                         "checkpoint boundary, so re-run recovery shows)")
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--eta0", type=float, default=0.5)
    ap.add_argument("--async-writes", action="store_true",
                    help="use SnapshotStore(async_writes=True): snapshot "
                         "writes drain on a background thread while the "
                         "epoch loop runs; all recovery assertions "
                         "unchanged")
    ap.add_argument("--chaos", action="store_true",
                    help="run the self-healing gauntlet (NaN + crashes + "
                         "corrupt snapshot + persistent straggler) instead")
    ap.add_argument("--ledger-out", default="elastic-chaos-ledger.json")
    ap.add_argument("--events-out", default="elastic-chaos-events.jsonl",
                    help="--chaos: run-event JSONL log (RunRecorder)")
    args = ap.parse_args(argv)
    if args.chaos:
        return run_chaos(args)

    prob = make_classification(m=128, d=96, density=0.1, loss="hinge",
                               lam=1e-3, seed=0)
    print(f"m={prob.m} d={prob.d} |Omega|={int(prob.nnz)}; p=8 mesh, "
          f"checkpoint every {args.ckpt_every}, crash every "
          f"{args.fault_every}")

    # uninterrupted reference trajectory
    ref = ShardedDSO(prob, make_dso_mesh(8), impl="auto", schedule="cyclic",
                     seed=5)
    ref.run_epochs(args.epochs, args.eta0)
    w_ref = np.asarray(ref.w_full())

    with tempfile.TemporaryDirectory() as ckpt_dir:
        store = SnapshotStore(ckpt_dir, async_writes=args.async_writes)

        # -- phase 1: crash storm, exact recovery ------------------------
        sup = Supervisor(store, checkpoint_every=args.ckpt_every,
                         eta0=args.eta0,
                         fault_plan=periodic_crashes(args.fault_every,
                                                     args.epochs))
        opt, log = sup.run_sharded(prob, args.epochs, mesh=make_dso_mesh(8),
                                   impl="auto", schedule="cyclic", seed=5)
        for ev in log:
            print(f"  [supervisor] {ev}")
        diff = float(np.abs(np.asarray(opt.w_full()) - w_ref).max())
        crashes = sum(ev["kind"] == "crash" for ev in log)
        print(f"phase 1: {crashes} crash(es), max |w - w_uninterrupted| = "
              f"{diff}")
        assert diff == 0.0, "crash recovery must be bit-identical"

        # -- phase 2: live reshard 8 -> 4 + one more crash ---------------
        total = args.epochs + 2 * args.ckpt_every
        sup2 = Supervisor(store, checkpoint_every=args.ckpt_every,
                          eta0=args.eta0,
                          fault_plan=(
                              FaultEvent(args.epochs, "reshard", 4),
                              FaultEvent(args.epochs + args.ckpt_every,
                                         "crash")))
        opt, log = sup2.run_sharded(prob, total, mesh=make_dso_mesh(8),
                                    impl="auto", schedule="cyclic", seed=5)
        for ev in log:
            print(f"  [supervisor] {ev}")
        gaps = [h["gap"] for h in sup2.history]
        print(f"phase 2: resumed + resharded to p={opt.p}, epochs "
              f"{opt.epochs_done}; gap {gaps[0]:.4f} -> {gaps[-1]:.4f}")
        assert opt.p == 4 and opt.epochs_done == total
        assert gaps[-1] < gaps[0], "gap must keep shrinking across reshard"
    print("ELASTIC_OK")


if __name__ == "__main__":
    main()

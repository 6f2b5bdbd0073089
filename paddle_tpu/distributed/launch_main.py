"""python -m paddle_tpu.distributed.launch — multi-process / multi-host
launcher with supervision.

Reference: python/paddle/distributed/launch (controllers/collective.py
process management + fleet elastic restart). Each host runs
``--nproc_per_node`` worker processes under a supervisor: the gang shares
the PADDLE_* env contract, a crashed worker tears down (and with
``--max_restarts`` relaunches) the whole local gang — the reference
launcher's watch/restart loop. ``--nproc_per_node 1`` (TPU pods: one
process per host under the jax multi-controller runtime) execs in-process.

On a TPU host use ``--nproc_per_node 1``: the repo's design is ONE process
driving every local chip. The gang's workers each inherit the full
environment, so on a four-chip host each would claim all four chips, and
a chip belongs to one process at a time. ``chip_smoke.py --chips 4`` runs
the hybrid-parallel step that way and never goes through this launcher;
gangs of more than one worker are for CPU workers and tests.
"""
from __future__ import annotations

import argparse
import os
import runpy
import signal
import subprocess
import sys
import time


def _parse(argv):
    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--nnodes", type=int,
                        default=int(os.environ.get("PADDLE_TRAINERS_NUM", 1)))
    parser.add_argument("--node_rank", type=int,
                        default=int(os.environ.get("PADDLE_TRAINER_ID", 0)))
    parser.add_argument("--master", default=os.environ.get("PADDLE_MASTER", ""))
    parser.add_argument("--nproc_per_node", type=int, default=1)
    parser.add_argument("--max_restarts", type=int, default=0,
                        help="elastic-style gang relaunches on worker failure")
    parser.add_argument("--elastic", action="store_true",
                        help="on relaunch, workers resume from the latest "
                             "checkpoint (PADDLE_ELASTIC_* env contract)")
    parser.add_argument("--ckpt_dir", default=None,
                        help="checkpoint directory exported to workers as "
                             "PADDLE_ELASTIC_CKPT_DIR")
    parser.add_argument("--heartbeat_timeout", type=float, default=60.0,
                        help="seconds before a silent node counts as lost "
                             "(multi-node elastic membership)")
    parser.add_argument("--elastic_allow_scale_in", action="store_true",
                        help="if the SAME worker slot fails twice in a row, "
                             "re-form the gang without it (re-ranked, "
                             "smaller world) instead of failing the job")
    parser.add_argument("--log_dir", default=None,
                        help="per-rank stdout/stderr files instead of inherit")
    parser.add_argument("script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    return parser.parse_args(argv)


def _run_inline(args):
    os.environ["PADDLE_TRAINERS_NUM"] = str(args.nnodes)
    os.environ["PADDLE_TRAINER_ID"] = str(args.node_rank)
    os.environ.setdefault("PADDLE_ELASTIC_ATTEMPT", "0")
    if args.elastic:
        os.environ["PADDLE_ELASTIC"] = "1"
    if args.ckpt_dir:
        os.environ["PADDLE_ELASTIC_CKPT_DIR"] = os.path.abspath(
            args.ckpt_dir)
    if args.master:
        os.environ["PADDLE_MASTER"] = args.master
    sys.argv = [args.script] + args.script_args
    runpy.run_path(args.script, run_name="__main__")
    return 0


def _spawn_gang(args, slots=None, attempt=0):
    """Start workers for the given local slot ids (re-ranked contiguously
    after scale-in); returns list of (slot, proc, logfile)."""
    slots = list(range(args.nproc_per_node)) if slots is None else slots
    world = args.nnodes * len(slots)
    procs = []
    for new_local, slot in enumerate(slots):
        rank = args.node_rank * len(slots) + new_local
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_LOCAL_RANK": str(new_local),
            "PADDLE_LOCAL_SIZE": str(len(slots)),
            "PADDLE_ELASTIC_ATTEMPT": str(attempt),
            "PADDLE_WORKER_SLOT": str(slot),
        })
        if args.elastic:
            env["PADDLE_ELASTIC"] = "1"
        if args.ckpt_dir:
            env["PADDLE_ELASTIC_CKPT_DIR"] = os.path.abspath(args.ckpt_dir)
        if args.master:
            env["PADDLE_MASTER"] = args.master
        log = None
        kw = {}
        if args.log_dir:
            os.makedirs(args.log_dir, exist_ok=True)
            # append: a restarted gang must not truncate the previous
            # attempt's crash traceback
            log = open(os.path.join(args.log_dir, f"worker.{slot}.log"), "a")
            kw = {"stdout": log, "stderr": subprocess.STDOUT}
        p = subprocess.Popen(
            [sys.executable, args.script] + args.script_args, env=env, **kw)
        procs.append((slot, p, log))
    return procs


def _supervise(procs, heartbeat=None, beat_every=5.0):
    """Wait for the gang; first failure terminates the rest.
    Returns (rc, failed_slots): every slot found dead-nonzero in the SAME
    poll tick as the first detected failure — collateral deaths of later
    ticks (collectives failing after a peer vanished) are not blamed.
    """
    try:
        last_beat = 0.0
        while True:
            alive = False
            failed = []
            rc_first = 0
            for slot, p, _ in procs:
                rc = p.poll()
                if rc is None:
                    alive = True
                elif rc != 0:
                    failed.append(slot)
                    rc_first = rc_first or rc
            if failed:
                for _, q, _l in procs:
                    if q.poll() is None:
                        q.terminate()
                deadline = time.monotonic() + 10
                for _, q, _l in procs:
                    try:
                        q.wait(timeout=max(0.1, deadline - time.monotonic()))
                    except subprocess.TimeoutExpired:
                        q.kill()
                return rc_first, failed
            if not alive:
                return 0, []
            if heartbeat is not None \
                    and time.monotonic() - last_beat > beat_every:
                heartbeat()
                last_beat = time.monotonic()
            time.sleep(0.2)
    finally:
        for _, _p, log in procs:
            if log is not None:
                log.close()


def main(argv=None):
    args = _parse(argv)
    if args.nproc_per_node <= 1:
        return _run_inline(args)

    # multi-node elastic: file-heartbeat membership on the (shared)
    # checkpoint filesystem re-ranks surviving nodes between attempts —
    # the reference elastic manager's etcd watch, without etcd. Per-slot
    # scale-in stays single-node (cross-node slot drop would need a
    # coordinated world size; membership handles whole-node loss instead).
    membership = None
    if args.elastic and args.nnodes > 1 and args.ckpt_dir:
        from .elastic import ElasticMembership
        membership = ElasticMembership(
            os.path.join(os.path.abspath(args.ckpt_dir), ".membership"),
            node_id=f"{args.node_rank:06d}",
            timeout=args.heartbeat_timeout).register()
    if args.elastic_allow_scale_in and args.nnodes > 1:
        print("[launch] --elastic_allow_scale_in is per-node; with "
              "nnodes>1 node loss is handled by membership re-rank, "
              "slot scale-in is disabled", file=sys.stderr)
        args.elastic_allow_scale_in = False

    attempts = args.max_restarts + 1
    rc = 1
    slots = list(range(args.nproc_per_node))
    last_failed = []
    shutting_down = {"flag": False}
    for attempt in range(attempts):
        if attempt:
            print(f"[launch] gang failed (rc={rc}, slots={last_failed}); "
                  f"restart {attempt}/{args.max_restarts}"
                  + (" (resume from checkpoint)" if args.elastic else ""),
                  file=sys.stderr)
        if membership is not None:
            membership.heartbeat()
            new_rank, new_nnodes = membership.rerank()
            if new_rank is None:
                print("[launch] this node is no longer in the membership; "
                      "exiting", file=sys.stderr)
                return rc
            args.node_rank, args.nnodes = new_rank, new_nnodes
        procs = _spawn_gang(args, slots=slots, attempt=attempt)

        def _forward(signum, frame):
            shutting_down["flag"] = True
            for _, p, _l in procs:
                if p.poll() is None:
                    p.send_signal(signum)

        old = signal.signal(signal.SIGTERM, _forward)
        try:
            rc, failed = _supervise(
                procs, heartbeat=(membership.heartbeat
                                  if membership is not None else None),
                # refresh well inside the staleness window so a live node
                # can never read as lost between beats
                beat_every=max(0.5, min(5.0, args.heartbeat_timeout / 3)))
        finally:
            signal.signal(signal.SIGTERM, old)
        if rc == 0:
            return 0
        if shutting_down["flag"]:
            # operator shutdown, not a worker fault: no relaunch
            return rc
        # scale-in: the same single slot failing twice in a row is a bad
        # worker (reference elastic manager drops lost nodes and re-ranks
        # the remainder)
        if (args.elastic_allow_scale_in and len(failed) == 1
                and failed == last_failed and len(slots) > 1):
            slots = [s for s in slots if s != failed[0]]
            print(f"[launch] slot {failed[0]} failed twice; scaling in to "
                  f"{len(slots)} workers (re-ranked)", file=sys.stderr)
        last_failed = failed
    if membership is not None:
        membership.leave()
    return rc


if __name__ == "__main__":
    sys.exit(main())

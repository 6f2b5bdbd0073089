"""Reference spelling: python/paddle/distributed/spawn.py."""


def spawn(func, args=(), nprocs=-1, join=True, **kwargs):
    """Reference: distributed/spawn.py — run ``func`` in worker processes.

    nprocs <= 1 runs inline (the usual TPU case: one process per host, XLA
    owns every local device). nprocs > 1 starts real spawn processes with
    the PADDLE_* env contract; workers are pinned to the CPU platform (a
    chip belongs to one process at a time, and the parent may hold it)."""
    if nprocs is None or nprocs <= 1:
        func(*args)
        return

    import multiprocessing
    import os

    ctx = multiprocessing.get_context("spawn")
    saved = {k: os.environ.get(k)
             for k in ("JAX_PLATFORMS", "PADDLE_TRAINERS_NUM",
                       "PADDLE_TRAINER_ID")}
    procs = []
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
        for rank in range(nprocs):
            os.environ["PADDLE_TRAINER_ID"] = str(rank)
            # non-daemon (reference behavior): workers may start their own
            # children (multiprocess DataLoader) and survive join=False
            p = ctx.Process(target=func, args=args, daemon=False)
            p.start()
            procs.append(p)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if join:
        for p in procs:
            p.join()
        bad = [p.exitcode for p in procs if p.exitcode != 0]
        if bad:
            raise RuntimeError(f"spawn workers failed: exitcodes {bad}")
    return procs


__all__ = ["spawn"]

"""Driver ``train``: the compiled Fleet train step, fed by the program's
DataLoader with a fresh batch every step.

The path is the one a user takes (and ``chip_smoke.run_trainer`` took):
``fleet.init`` -> ``distributed_model`` -> ``distributed_optimizer(AdamW)
.make_train_step``. The traffic file gives ``batch``, ``seqlen``, the
optimizer's numbers and ``warmup_steps``.

Steps are dispatched one ahead: while the device runs step ``i`` the host
fetches the loss of step ``i - 1`` and loads batch ``i + 1``, so the device
waits for the host only where the host is slower than a step. The window
closes on the fetched loss of the last step dispatched inside it.
"""
from __future__ import annotations

import math
import threading
import time

import numpy as np

from benchmarks import arithmetic, models, stats, traffic as traffic_mod
from benchmarks.readers import end_to_end

KEYS = {"driver", "about", "batch", "seqlen", "optimizer", "warmup_steps"}


class Driver:
    def __init__(self, run):
        self.run = run
        self.stop = threading.Event()

    # -- the input pipeline: paddle_tpu.io.DataLoader, one worker ---------

    def _loader(self, vocab):
        from paddle_tpu.io import DataLoader, IterableDataset

        run, stop = self.run, self.stop

        class Tokens(IterableDataset):
            def __iter__(self):
                for row in traffic_mod.token_batches(run.traffic, vocab,
                                                     run.seed):
                    if stop.is_set():
                        return
                    yield row, row

        return iter(DataLoader(Tokens(), batch_size=run.traffic["batch"],
                               num_workers=1))

    def setup(self):
        from paddle_tpu import optimizer as optim
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet import DistributedStrategy

        run, t = self.run, self.run.traffic
        traffic_mod.known(t, KEYS, "traffic of driver train")
        traffic_mod.known(t["optimizer"], {"name", "learning_rate",
                                           "weight_decay"}, "optimizer")
        if t["optimizer"]["name"] != "AdamW":
            raise ValueError(f"unknown optimizer {t['optimizer']['name']!r}")
        ref = models.reference(run.config)
        strategy = DistributedStrategy()
        fleet.init(is_collective=True, strategy=strategy)
        model = fleet.distributed_model(models.build(run.config, run.seed))
        run.phase("model")
        self.batches = self._loader(run.config["vocab_size"])
        first = next(self.batches)

        # the reference's loss and gradients on the first batch and the
        # initial weights, while no optimizer state is on the device yet;
        # the step donates the weights, so the rows compared go to the host
        ids = np.asarray(first[0]._data)
        initial = models.weights(model)
        want, grads = ref.loss_and_gradients(initial, run.config, ids, ids)
        grads = {name: np.asarray(g) for name, g in grads.items()}
        before = {name: np.asarray(initial[name][:len(g)])
                  for name, g in grads.items()}
        del initial
        run.phase("reference")

        o = t["optimizer"]
        opt = fleet.distributed_optimizer(
            optim.AdamW(learning_rate=o["learning_rate"],
                        weight_decay=o["weight_decay"],
                        parameters=model.parameters()), strategy=strategy)
        self.step = opt.make_train_step(model,
                                        lambda m, i, l: m(i, labels=l))
        run.phase("optimizer_and_step_built")
        got = loss = float(np.asarray(self.step(*first)._data))
        updated = models.weights(model)
        update = ref.update_agreement(
            before, {name: np.asarray(updated[name][:len(g)])
                     for name, g in grads.items()}, grads,
            o["learning_rate"])
        del updated, before, grads
        run.phase("first_step")
        for _ in range(t["warmup_steps"] - 1):
            loss = float(np.asarray(self.step(*next(self.batches))._data))
        run.phase("warmup")
        self.tokens_per_step = t["batch"] * t["seqlen"]
        flops = arithmetic.train_flops_per_token(run.config, t["seqlen"])
        run.info("reference", first_loss=got, reference_loss=want,
                 gap=abs(got - want), tolerance=ref.LOSS_TOL,
                 loss_after_warmup=loss,
                 first_update_share_against_gradient_and_size_over_lr=update,
                 share_tolerance=ref.SIGN_TOL, size_tolerance=ref.SIZE_TOL)
        run.info("constants", tokens_per_step=self.tokens_per_step,
                 params=arithmetic.total_params(run.config),
                 matmul_params=arithmetic.matmul_params(run.config),
                 train_flops_per_token=flops)
        self.flops_per_token = flops
        run.compared.update({
            "first_loss_gap": {"value": abs(got - want),
                               "at_most": ref.LOSS_TOL},
            "least_share_against_gradient": {
                "value": min(share for share, _ in update.values()),
                "at_least": ref.SIGN_TOL},
            "largest_size_off_lr": {
                "value": max(abs(size - 1.0) for _, size in update.values()),
                "at_most": ref.SIZE_TOL}})
        return (math.isfinite(got) and abs(got - want) <= ref.LOSS_TOL
                and all(share >= ref.SIGN_TOL
                        and abs(size - 1.0) <= ref.SIZE_TOL
                        for share, size in update.values()))

    def window(self, seconds, tracer):
        run, spans = self.run, self.run.spans
        losses, done = [], []
        pending = None
        i = 0
        t_open = time.perf_counter()
        while True:
            since = time.perf_counter() - t_open
            if since >= seconds + tracer.extension:
                break
            tracer.tick(since)
            with spans.span("loader", i):
                batch = next(self.batches)
            with spans.span("dispatch", i):
                loss = self.step(*batch)
            if pending is not None:
                with spans.span("fetch", i - 1):
                    losses.append(float(np.asarray(pending._data)))
                done.append(time.perf_counter())
            pending = loss
            i += 1
        with spans.span("fetch", i - 1):
            losses.append(float(np.asarray(pending._data)))
        t_close = time.perf_counter()
        done.append(t_close)
        tracer.stop()
        bad = sum(not math.isfinite(v) for v in losses)
        return {"window": (t_open, t_close), "attempted": i, "failed": bad,
                "steps": i, "step_done": done, "losses": losses,
                "tokens_per_step": self.tokens_per_step,
                "flops_per_token": self.flops_per_token}

    def report(self):
        """Step times and the model FLOP/s utilisation: the end-to-end
        rate times a constant of the cell (no metric of its own)."""
        run, s = self.run, self.run.samples
        rate = end_to_end.train_tokens_per_s(run)
        gaps = [b - a for a, b in zip(s["step_done"], s["step_done"][1:])]
        info = {"steps": s["steps"], "tokens_per_s": rate,
                "step_ms_median": (stats.median(gaps) or 0.0) * 1e3,
                "step_ms_p95": (stats.percentile(gaps, 95) or 0.0) * 1e3,
                "last_loss": s["losses"][-1],
                "train_flops_per_token": s["flops_per_token"]}
        if run.on_device:
            peak = arithmetic.peaks(run.device_kind)["bf16_flops_per_s"]
            info["model_flops_utilisation_pct"] = \
                100.0 * rate * s["flops_per_token"] / (peak * run.chips)
            info["peak_bf16_flops_per_s"] = peak
        run.info("train", **info)

    def close(self):
        # let the loader's producer thread run out: it stops at the flag
        self.stop.set()
        for _ in self.batches:
            pass

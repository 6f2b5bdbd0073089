#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two hot paths once, through the entry points a user calls, at
the full widths of Llama-2-7B (hidden 4096, intermediate 11008, 32 heads x
128, vocab 32000, bf16). Only depth is cut, to what one 16 GB chip holds,
and every cut is printed. Weights are random, made from ``--seed``.

    python chip_smoke.py             # one chip: kernels, trainer, server
    python chip_smoke.py --chips 4   # four chips from ONE process: the
                                     # hybrid-parallel step and Engine(tp=4)
                                     # against their one-device runs, and
                                     # nothing else

One JSON object per phase on stdout; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed check in any phase is a traceback, a non-zero exit and no such
line; so is a run in which jax finds no TPU. Times printed here are plain
information (set-up includes compilation), not benchmark results.

The phases are importable functions of a config and sizes, so
tests/test_chip_smoke_rehearsal.py runs them small on virtual CPU devices;
``main()`` itself has no CPU mode.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import statistics
import sys
import time

import numpy as np

# max |got - ref| / max |ref| allowed between a compiled kernel and its jnp
# reference on the same bf16 inputs: bf16 keeps 8 bits (2^-8 = 0.4 %), and
# the references round their probabilities or activations to bf16 at other
# points of the chain than the kernels do
BF16_TOL = 3e-2
# a mean cross-entropy is an fp32 reduction of bf16 logits: two layouts of
# the same model differ by reduction order, far inside this
LOSS_TOL = 2e-2
# two bf16 programs of the same math (the engine's bucket-padded prefill and
# 8-slot decode, generate()'s exact-length scan, a tensor-parallel ring)
# each round every logit to the bf16 grid, 256 values to a binade, so the
# largest of 32000 logits is often tied with the next: such scores cannot be
# told apart, and either token is a correct answer. Every parting seen on
# the chip was an exact tie under a third program (0 steps); the bound is
# that and one step more, at the size of the largest logit. Greedy only: a
# sampled request that parts from its reference fails outright.
NEAR_TIE_ULPS = 1

ONE_CHIP = dict(
    train=dict(depth=2, batch=2, seqlen=2048, steps=6, warmup=2),
    serve=dict(depth=8, max_len=1024, prefill_chunk=128, prefix=32,
               tails=(8, 8, 60), solo=20, long=168, max_new=8),
)
FOUR_CHIPS = dict(
    train=dict(ONE_CHIP["train"], hybrid=dict(sharding=2, tp=2)),
    serve=dict(ONE_CHIP["serve"], tp=4),
)


class SmokeFailure(AssertionError):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def emit(phase, **info):
    print(json.dumps({"phase": phase, **info}), flush=True)


class Builds:
    """Programs built by XLA in this process, from jax's own monitoring
    events: ``count`` programs went through the backend's compile entry,
    of which ``cache_hits`` were read back from the persistent cache."""

    def __init__(self):
        import jax

        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.count = 0
        self.cache_hits = 0

    def snapshot(self):
        return {"programs_built": self.count,
                "from_compile_cache": self.cache_hits}


def memory(devices):
    """Per-device allocator statistics, where the backend reports them
    (the CPU backend does not)."""
    out = []
    for d in devices:
        st = d.memory_stats()
        out.append(None if st is None else
                   {"bytes_in_use": st["bytes_in_use"],
                    "peak_bytes_in_use": st["peak_bytes_in_use"]})
    return out


def live_bytes():
    """What this process still holds on its devices: a phase that leaks
    its model would starve the next one."""
    import jax

    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def versions():
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def cut(cfg, sizes):
    """``cfg`` at ``sizes["depth"]`` layers, widths untouched, and the
    rest of ``sizes`` as the phase's keyword arguments."""
    kw = dict(sizes)
    return dataclasses.replace(cfg, num_hidden_layers=kw.pop("depth")), kw


def describe(cfg, full_depth):
    return {"hidden": cfg.hidden_size, "intermediate": cfg.intermediate_size,
            "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads,
            "head_dim": cfg.hidden_size // cfg.num_attention_heads,
            "vocab": cfg.vocab_size, "dtype": cfg.dtype,
            "depth": cfg.num_hidden_layers, "depth_published": full_depth}


# ---------------------------------------------------------------------------
# trainer: fleet.init -> distributed_model -> distributed_optimizer(AdamW)
#          .make_train_step, batches through paddle.io.DataLoader
# ---------------------------------------------------------------------------

def _token_loader(vocab, batch, seqlen, n_batches, seed):
    """A DataLoader over a synthetic token stream that repeats one fixed
    batch: ``batch`` sequences drawn from ``seed``, served in order."""
    from paddle_tpu.io import DataLoader, IterableDataset
    from paddle_tpu.runtime import native

    rows = np.random.default_rng(seed).integers(
        0, vocab, (batch, seqlen)).astype(np.int32)

    class Tokens(IterableDataset):
        def __iter__(self):
            for i in range(n_batches * batch):
                yield rows[i % batch], rows[i % batch]

    # one worker over an iterable dataset: the loader's single-producer
    # path, served by the native prefetch ring where it builds and by a
    # Python thread queue where it does not
    try:
        native.load_lib()
        served_by = "native prefetch ring"
    except ImportError as e:
        served_by = f"python thread queue ({e})"
    return DataLoader(Tokens(), batch_size=batch, num_workers=1), served_by


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def run_trainer(cfg, *, batch, seqlen, steps, warmup, seed, builds, platform,
                devices, hybrid=None):
    """A few compiled train steps on one fixed batch; returns what it
    saw. ``devices`` is what the mesh fleet.init builds must span, in its
    order; ``hybrid`` the fleet degrees (sharding > 1 runs stage 3)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as optim
    from paddle_tpu.distributed import fleet, mesh as mesh_mod
    from paddle_tpu.distributed.fleet import (DistributedStrategy,
                                              HybridCommunicateGroup)
    from paddle_tpu.nn.functional.attention import attention_path
    from paddle_tpu.text.models.llama import LlamaForCausalLM

    t0 = time.perf_counter()
    builds.reset()
    devices = list(devices)
    hybrid = dict(hybrid or {})
    paddle.seed(seed)
    strategy = DistributedStrategy()
    strategy.hybrid_configs.update(
        sharding_degree=hybrid.get("sharding", 1),
        mp_degree=hybrid.get("tp", 1))
    if hybrid.get("sharding", 1) > 1:
        strategy.sharding = True
        strategy.sharding_configs["sharding_stage"] = 3
    hcg = fleet.init(is_collective=True, strategy=strategy)
    if not hybrid and len(devices) < len(jax.devices()):
        # the one-device comparator on a host of several chips: fleet.init
        # spans every device jax has (no degrees: dp over all of them), so
        # this run alone names its topology itself
        hcg = fleet._hcg = HybridCommunicateGroup(
            strategy, mesh=mesh_mod.build_mesh(devices=devices))
    mesh = mesh_mod.get_mesh()
    check(mesh is hcg.mesh and list(mesh.devices.flat) == devices,
          f"fleet's mesh spans {list(mesh.devices.flat)}, given {devices}")

    model = fleet.distributed_model(LlamaForCausalLM(cfg))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = fleet.distributed_optimizer(
        optim.AdamW(learning_rate=3e-4, weight_decay=0.01,
                    parameters=model.parameters()), strategy=strategy)
    step = opt.make_train_step(model, lambda m, i, l: m(i, labels=l))

    loader, served_by = _token_loader(cfg.vocab_size, batch, seqlen, steps,
                                      seed)
    losses, step_s = [], []
    info = {}
    for i, (ids, labels) in enumerate(loader):
        if i == 0:
            lowered = step.lower(ids, labels)
            info["kernel_in_lowered_step"] = \
                "tpu_custom_call" in lowered.as_text()
            compiled = lowered.compile()
            text = compiled.as_text()
            info["collectives"] = [c for c in _COLLECTIVES if c in text]
            ma = compiled.memory_analysis()
            if ma is not None:
                info["planned_bytes_per_device"] = (
                    ma.argument_size_in_bytes + ma.output_size_in_bytes
                    - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        if i == warmup:
            info["setup_s"] = round(time.perf_counter() - t0, 2)
            info["setup"] = builds.snapshot()
            builds.reset()
        t1 = time.perf_counter()
        losses.append(float(np.asarray(step(ids, labels)._data)))
        step_s.append(time.perf_counter() - t1)
    info.update(
        params=n_params, tokens_per_step=batch * seqlen, losses=losses,
        steady_step_s=statistics.median(step_s[warmup:]),
        programs_built_after_warmup=builds.count,
        attention=attention_path(), loader=served_by,
        mesh={k: v for k, v in mesh.shape.items() if v > 1},
        devices=len(devices), memory=memory(devices))

    check(len(losses) == steps and steps - warmup >= 1,
          f"loader gave {len(losses)} batches, wanted {steps}")
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    check(abs(losses[0] - math.log(cfg.vocab_size)) < 0.5,
          f"step-0 loss {losses[0]} is not near ln(vocab) = "
          f"{math.log(cfg.vocab_size):.3f}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(builds.count == 0,
          f"{builds.count} programs were built after the warm-up steps")
    for name, p in model.named_parameters():
        held = p._data.sharding.device_set
        check({d.platform for d in held} == {platform},
              f"{name} lives on {sorted(d.platform for d in held)}")
        check(held == set(devices),
              f"{name} spans {len(held)} devices, not {len(devices)}")
    check(bool(info["collectives"]) == (len(devices) > 1),
          f"collectives in the compiled step: {info['collectives']} "
          f"on {len(devices)} devices")
    if platform == "tpu":
        check(info["attention"]["path"] == "flash",
              f"attention took {info['attention']}")
        check(info["kernel_in_lowered_step"],
              "no tpu_custom_call in the lowered train step")
        share = [m["bytes_in_use"] for m in info["memory"]]
        check(min(share) > 0 and max(share) < 2 * min(share),
              f"devices do not hold comparable shares: {share}")
    return info


# ---------------------------------------------------------------------------
# server: serving.Engine over text.generation, against model.generate()
# ---------------------------------------------------------------------------

def _requests(vocab, *, prefix, tails, solo, long, seed):
    """Mixed-length prompts: ``tails`` and ``long`` continue one shared
    system prompt of ``prefix`` tokens, ``solo`` stands alone; ``long``
    exceeds the prefill chunk."""
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(0, vocab, (n,)).astype(np.int32)

    system = toks(prefix)
    prompts = [np.concatenate([system, toks(n)]) for n in tails]
    prompts.append(toks(solo))
    prompts.append(np.concatenate([system, toks(long)]))
    return prompts


def _drive(engine, prompts, gens):
    """Staggered submission: the first request's prefill lands in the
    radix index before its sharers arrive, later ones join mid-decode."""
    handles = [engine.submit(prompts[0], **gens[0])]
    engine.step()
    for p, g in zip(prompts[1:], gens[1:]):
        handles.append(engine.submit(p, **g))
        engine.step()
    engine.drain()
    return [list(h.tokens) for h in handles]


def _generate(model, prompt, **kw):
    import paddle_tpu as paddle

    out = model.generate(paddle.to_tensor(prompt[None]), **kw)
    return np.asarray(out._data)[0, len(prompt):].tolist()


def _near_tie(model, prompt, got, want, label):
    """Greedy ``got`` differs from ``want``: accept that only as bf16
    near-ties. Under one teacher-forced forward of the model over the
    engine's own sequence — a third program, layer by layer — every token
    the engine emitted, and the reference's token where the two part, must
    score within NEAR_TIE_ULPS bf16 steps of the best. Returns the
    parting."""
    import jax.numpy as jnp

    import paddle_tpu as paddle

    seq = np.concatenate([prompt, np.asarray(got, np.int32)])
    logits = model(paddle.to_tensor(seq[None]))._data[0]
    zs = np.asarray(logits[len(prompt) - 1:len(seq) - 1].astype(jnp.float32))
    first = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
    for i, z in enumerate(zs):
        tau = NEAR_TIE_ULPS * 2.0 ** (
            math.floor(math.log2(float(np.abs(z).max()))) - 7)
        for tok in {got[i]} | ({want[i]} if i == first else set()):
            check(z[tok] >= z.max() - tau,
                  f"{label}: token {tok} at index {i} of {got} (reference "
                  f"{want}) scores {z.max() - z[tok]} below the best, "
                  f"more than {tau}")
        if i == first:
            parting = {"index": i, "engine": got[i], "reference": want[i],
                       "gap": float(abs(z[got[i]] - z[want[i]])),
                       "allowed": tau}
    return parting


def _compare(model, prompts, got, want, label, *, exact=False):
    """Token identity of ``got`` with ``want``, request by request; where
    they part, the near-tie that explains it (``exact``: none does)."""
    check(not exact or got == want, f"{label}: {got} != {want}")
    return {"equal": got == want,
            "near_ties": [dict(_near_tie(model, p, g, w, label), request=i)
                          for i, (p, g, w) in enumerate(
                              zip(prompts, got, want)) if g != w]}


def _engine_pass(model, prompts, gens, want, builds, label, **engine_kw):
    """One engine, gone when this returns: build it, serve ``prompts``
    twice, compare with ``want``, count its programs — buckets + decode
    (+ chunk) — and say what its lowered decode program holds. Returns
    what it saw, and the tokens."""
    from paddle_tpu.analysis.engine_support import lower_decode_program
    from paddle_tpu.serving import Engine

    engine = Engine(model, **engine_kw)
    builds.reset()
    t0 = time.perf_counter()
    got = _drive(engine, prompts, gens)
    cold_s = time.perf_counter() - t0
    cold = builds.snapshot()
    builds.reset()
    t0 = time.perf_counter()
    again = _drive(engine, prompts, gens)
    warm_s = time.perf_counter() - t0
    rebuilt = builds.count
    st = engine.stats()
    decode_text = lower_decode_program(engine)
    expected = len(engine.buckets_seen) + 1 + int(engine.chunk_used)
    info = {"engine": label,
            "vs_generate": _compare(model, prompts, got, want, label,
                                    exact=engine_kw.get("do_sample", False)),
            "programs": cold, "programs_expected": expected,
            "programs_built_second_pass": rebuilt,
            "prefill_buckets": st["prefill_buckets"],
            "chunk_program": st["chunk_program"],
            "prefix_hit_tokens": st["prefix_hit_tokens"],
            "first_pass_s": round(cold_s, 2),
            "second_pass_s": round(warm_s, 3),
            "kv_cache_bytes": st["kv_cache_bytes"],
            "kernel_in_decode": "tpu_custom_call" in decode_text,
            "ring_in_decode": "collective_permute" in decode_text}
    if engine.tp > 1:
        info["mesh"] = st["mesh"]
        for name, w in engine._w.items():
            check(len(w.sharding.device_set) == engine.tp,
                  f"{label}: weight {name} spans "
                  f"{len(w.sharding.device_set)} devices, not {engine.tp}")
    check(again == got, f"{label}: second pass differs: {again} != {got}")
    check(engine.cache.check_refcounts(), f"{label}: block refcounts leak")
    check(rebuilt == 0,
          f"{label}: {rebuilt} programs built on the warm engine")
    check(cold["programs_built"] == expected,
          f"{label}: built {cold} programs, expected {expected}")
    return info, got


def run_server(cfg, *, max_len, prefill_chunk, prefix, tails, solo, long,
               max_new, seed, builds, platform, tp=1):
    """Engines over one model against ``model.generate()``: greedy with
    paged KV, chunked prefill and prefix sharing (its decode program
    holds the paged-attention kernel on a TPU and nowhere else);
    sampled; and, with ``tp > 1``, greedy again tensor-parallel, which is
    also held against the first engine's tokens."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.llama import LlamaForCausalLM

    t0 = time.perf_counter()
    builds.reset()
    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    for name, p in model.named_parameters():
        check({d.platform for d in p._data.devices()} == {platform},
              f"{name} lives on {p._data.devices()}")

    prompts = _requests(cfg.vocab_size, prefix=prefix, tails=tails,
                        solo=solo, long=long, seed=seed)
    greedy = [dict(max_new_tokens=max_new) for _ in prompts]
    # the sampled engine serves the two sharers of equal length
    sampled = [dict(max_new_tokens=max_new, temperature=0.8, seed=seed + 11),
               dict(max_new_tokens=max_new, temperature=1.2, seed=seed + 7)]
    sample_kw = dict(do_sample=True, top_k=8)
    # references first, while no engine holds a second copy of the weights
    want_greedy = [_generate(model, p, **g) for p, g in zip(prompts, greedy)]
    want_sampled = [_generate(model, p, **sample_kw, **g)
                    for p, g in zip(prompts[:2], sampled)]
    info = {"lens": [len(p) for p in prompts], "max_new": max_new,
            "reference_s": round(time.perf_counter() - t0, 2),
            "reference": builds.snapshot(), "engines": []}

    geometry = dict(max_len=max_len, prefill_chunk=prefill_chunk)
    e, got_greedy = _engine_pass(model, prompts, greedy, want_greedy, builds,
                                 "greedy", **geometry)
    info["engines"].append(e)
    check(e["chunk_program"], "the long prompt did not take chunked prefill")
    check(e["prefix_hit_tokens"] > 0, "no prompt token came from the radix")
    check(e["kernel_in_decode"] == (platform == "tpu"),
          f"tpu_custom_call in the one-device decode program: "
          f"{e['kernel_in_decode']} on {platform}")
    check(not e["ring_in_decode"],
          "the one-device decode program holds a ring")

    info["engines"].append(_engine_pass(
        model, prompts[:2], sampled, want_sampled, builds, "sampled",
        **geometry, **sample_kw)[0])

    if tp > 1:
        label = f"tp={tp}"
        e, got = _engine_pass(model, prompts, greedy, want_greedy, builds,
                              label, **geometry, tp=tp)
        # engine against engine, directly: two near-ties with generate()
        # at different places are not yet one with each other
        e["vs_one_device_engine"] = _compare(
            model, prompts, got, got_greedy, f"{label} vs greedy engine")
        check(e["ring_in_decode"],
              "no collective_permute in the tensor-parallel decode program")
        info["engines"].append(e)
    info["total_s"] = round(time.perf_counter() - t0, 2)
    return info


# ---------------------------------------------------------------------------
# kernels: each one compiled (never interpret=True) against its jnp
#          reference on the same inputs — chip only
# ---------------------------------------------------------------------------

def _rel_err(got, ref):
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref))
                 / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))


def _kernel_cases(cfg, seqlen, max_len, seed):
    """(name, kernel fn, reference fn, args) at the shapes the model and
    the engine give these kernels. Outputs are pytrees of arrays."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import _xla_sdpa
    from paddle_tpu.nn.quant import quantize_int8
    from paddle_tpu.ops.pallas.chunk_attention import (
        chunk_attention, plain as chunk_plain)
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.paged_attention import (gathered,
                                                       paged_attention)
    from paddle_tpu.ops.pallas.fused_ce import (fused_ce_loss,
                                                fused_ce_reference)
    from paddle_tpu.ops.pallas.int8_matmul import (_quant_rows, int8_linear)
    from paddle_tpu.ops.pallas.ragged_matmul import (
        ragged_group_matmul, ragged_group_matmul_reference)

    rng = np.random.default_rng(seed)
    H, hd = cfg.num_attention_heads, cfg.hidden_size // cfg.num_attention_heads
    hidden, ff, vocab = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size

    def normal(shape, scale=1.0, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    q, k, v, ct = (normal((1, seqlen, H, hd)) for _ in range(4))

    def attn_grads(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32)
                           * ct.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def sdpa(q, k, v):
        return _xla_sdpa(q, k, v, causal=True)

    yield "flash_attention.fwd", flash, sdpa, (q, k, v)
    yield ("flash_attention.grad", attn_grads(flash), attn_grads(sdpa),
           (q, k, v))

    # the engine's pool: n_slots * max_len / block_size blocks + trash
    slots, bs = 8, 16
    mb = max_len // bs
    for n_kv, window in ((cfg.num_key_value_heads, 0), (8, 0), (4, 96)):
        pool = (slots * mb + 1, bs, n_kv, hd)
        yield (f"paged_attention.n_kv{n_kv}.window{window}",
               paged_attention, gathered,
               (normal((slots, H, hd)), normal(pool), normal(pool),
                jnp.asarray(rng.integers(1, pool[0], (slots, mb)), jnp.int32),
                jnp.asarray(rng.integers(0, mb * bs, (slots,)), jnp.int32),
                jnp.int32(window)))

    # a latent chunk body's tile (kimi-k2.6: 64 heads x 512 rows, 192
    # against 128, 2048 cached lines of 512 + 64 padded to 640), folded
    # into a state that has seen lines already
    def chunk_kernel(q, q_shared, lines, w, gpos, first, *carry):
        return chunk_attention(q, q_shared, lines, w, gpos, first, carry,
                               scale=0.1447)

    def chunk_reference(*args):
        return chunk_plain(*args, scale=0.1447)

    f32 = jnp.float32
    yield ("chunk_attention", chunk_kernel, chunk_reference,
           (normal((64, 512, 128)), normal((64, 512, 64)),
            normal((2048, 640)), normal((512, 64, 256), 512 ** -0.5),
            5000 + jnp.arange(512, dtype=jnp.int32), jnp.int32(4096),
            normal((64, 512), dtype=f32),
            1.0 + jnp.abs(normal((64, 512), dtype=f32)),
            normal((64, 512, 128), dtype=f32)))

    ce = (normal((seqlen, hidden)), normal((hidden, vocab), 0.02),
          jnp.asarray(rng.integers(0, vocab, (seqlen,)), jnp.int32))
    yield "fused_ce_loss.fwd", fused_ce_loss, fused_ce_reference, ce
    yield ("fused_ce_loss.grad", jax.grad(fused_ce_loss, argnums=(0, 1)),
           jax.grad(fused_ce_reference, argnums=(0, 1)), ce)

    wq, ws = quantize_int8(normal((hidden, ff), 0.02), axis=0)

    def int8_reference(x, wq, ws):
        xq, xs = _quant_rows(x)
        acc = jnp.dot(xq.astype(jnp.int32), wq.astype(jnp.int32),
                      preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * xs * ws).astype(jnp.bfloat16)

    yield ("int8_linear", int8_linear, int8_reference,
           (normal((seqlen, hidden)), wq, ws))

    counts = jnp.asarray([256, 0, 128, 256, 64, 8, 200, 31], jnp.int32)
    yield ("ragged_group_matmul", ragged_group_matmul,
           ragged_group_matmul_reference,
           (normal((8, 256, 2048)), normal((8, 2048, 1024), 0.02), counts))


def _stochastic_checks(cfg, seed):
    """The two PRNG kernels have no jnp twin to agree with bit for bit;
    their references are the properties of stochastic rounding."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.quant import quantize_int8_stochastic, stochastic_round

    w = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (cfg.hidden_size, cfg.hidden_size)), jnp.float32)

    def run(fn):
        lowered = jax.jit(lambda x: fn(x, seed=seed + 1)).lower(w)
        check("tpu_custom_call" in lowered.as_text(),
              f"no tpu_custom_call in {fn.__name__}")
        return lowered.compile()(w)

    r = run(stochastic_round).astype(jnp.float32)
    down = w.astype(jnp.bfloat16).astype(jnp.float32)   # nearest bf16
    ulp = jnp.abs(down) * 2.0 ** -7 + 1e-30
    on_grid = bool(jnp.all(jnp.abs(r - w) <= ulp))
    # signed towards larger magnitude, so that truncation shows as bias
    bias = float(jnp.mean((r - w) * jnp.sign(w)) / jnp.mean(jnp.abs(w)))
    moved = float(jnp.mean((r != down).astype(jnp.float32)))
    check(on_grid, "stochastic_round left the two bracketing bf16 values")
    check(abs(bias) < 1e-4, f"stochastic_round is biased: {bias}")
    check(0.1 < moved < 0.9,
          f"stochastic_round rounds to nearest ({moved} differ from it)")
    yield "stochastic_round", {"relative_bias": bias,
                               "differs_from_nearest": moved}

    qi, s = run(quantize_int8_stochastic)
    scale = float(s[0, 0])
    deq = qi.astype(jnp.float32) * scale
    worst = float(jnp.max(jnp.abs(deq - w)))
    bias = float(jnp.mean(deq - w)) / scale
    check(qi.dtype == jnp.int8 and worst <= scale * (1 + 1e-6),
          f"quantize_int8_stochastic error {worst} exceeds one step {scale}")
    check(abs(bias) < 1e-2, f"quantize_int8_stochastic is biased: {bias}")
    yield "quantize_int8_stochastic", {"max_err_steps": worst / scale,
                                       "bias_steps": bias}


def run_kernels(cfg, *, seqlen, max_len, seed, builds):
    import jax

    t0 = time.perf_counter()
    builds.reset()
    out = {}
    for name, kernel, reference, args in _kernel_cases(cfg, seqlen, max_len,
                                                       seed):
        lowered = jax.jit(kernel).lower(*args)
        check("tpu_custom_call" in lowered.as_text(),
              f"no tpu_custom_call in {name}")
        got = jax.tree_util.tree_leaves(lowered.compile()(*args))
        ref = jax.tree_util.tree_leaves(jax.jit(reference)(*args))
        errs = [_rel_err(g, r) for g, r in zip(got, ref)]
        out[name] = max(errs)
        check(len(got) == len(ref)
              and all(g.shape == r.shape for g, r in zip(got, ref)),
              f"{name}: outputs do not match the reference's shapes")
        check(all(math.isfinite(e) and e < BF16_TOL for e in errs),
              f"{name}: relative error {errs} against its reference "
              f"exceeds {BF16_TOL}")
    for name, seen in _stochastic_checks(cfg, seed):
        out[name] = seen
    return {"tolerance": BF16_TOL, "rel_err": out,
            "total_s": round(time.perf_counter() - t0, 2),
            **builds.snapshot()}


# ---------------------------------------------------------------------------

def run_four_devices(cfg, sizes, *, seed, builds, platform, devices):
    """The path across chips and what it is compared with, one after the
    other on the same devices: the hybrid-parallel step against the same
    step on ``devices[:1]``, and Engine(tp=N) against Engine() inside
    run_server. Returns one info dict per phase."""
    tcfg, train = cut(cfg, sizes["train"])
    hybrid = run_trainer(tcfg, **train, seed=seed, builds=builds,
                         platform=platform, devices=devices)
    after_hybrid = live_bytes()
    train.pop("hybrid")
    single = run_trainer(tcfg, **train, seed=seed, builds=builds,
                         platform=platform, devices=devices[:1])
    gap = abs(hybrid["losses"][0] - single["losses"][0])
    check(gap < LOSS_TOL,
          f"first-step loss {hybrid['losses'][0]} on {hybrid['mesh']} vs "
          f"{single['losses'][0]} on one device: {gap} apart")
    yield "trainer.hybrid", dict(hybrid, live_bytes_after=after_hybrid,
                                 first_loss_gap_to_one_device=gap)
    yield "trainer.one_device", dict(single, live_bytes_after=live_bytes())
    scfg, serve = cut(cfg, sizes["serve"])
    yield "server.tp", dict(
        run_server(scfg, **serve, seed=seed, builds=builds,
                   platform=platform), live_bytes_after=live_bytes())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from paddle_tpu.framework.device import use_compile_cache
    from paddle_tpu.text.models.llama import LLAMA2_7B

    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; jax found only {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke.py --chips {args.chips} needs exactly that many "
              f"devices; jax found {len(devices)}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    cfg, full_depth = LLAMA2_7B, LLAMA2_7B.num_hidden_layers
    builds = Builds()
    common = dict(seed=args.seed, builds=builds, platform="tpu")
    emit("start", device=device, compile_cache=cache_dir, seed=args.seed,
         **versions())

    sizes = FOUR_CHIPS if args.chips == 4 else ONE_CHIP
    (tcfg, train), (scfg, serve) = (cut(cfg, sizes[k])
                                    for k in ("train", "serve"))
    emit("cuts", train=describe(tcfg, full_depth),
         serve=describe(scfg, full_depth), sizes=sizes)
    if args.chips == 4:
        for phase, info in run_four_devices(cfg, sizes, devices=devices,
                                            **common):
            emit(phase, **info)
    else:
        emit("kernels", **run_kernels(cfg, seqlen=train["seqlen"],
                                      max_len=serve["max_len"],
                                      seed=args.seed, builds=builds))
        emit("trainer", **run_trainer(tcfg, **train, devices=devices,
                                      **common),
             live_bytes_after=live_bytes())
        emit("server", **run_server(scfg, **serve, **common),
             live_bytes_after=live_bytes())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

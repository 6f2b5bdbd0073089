"""Speculative decoding for the serving engine: draft-verify with
token-identical acceptance (ROADMAP item 4(a)).

Latency-shaped traffic pays one fused target step per token; speculative
decoding (Leviathan et al., arXiv 2211.17192) spends draft flops to
collapse up to ``k`` tokens into ONE verify pass. Two draft modes:

* ``SpecConfig(draft=model)`` — a small same-family model with a paged
  KV pool of its own (a fixed run of blocks a slot) proposes ``k``
  greedy tokens per round (k+1 fused draft decode steps, so the draft
  KV never develops holes on a full accept);
* ``SpecConfig(draft="ngram")`` — a draft-FREE variant in the spirit of
  lookahead/prompt-lookup decoding (Fu et al., arXiv 2402.02057): a
  host-side n-gram index over each request's prompt + emitted tokens
  proposes the continuation that followed the most recent occurrence of
  the current suffix. Zero extra XLA programs, zero extra flops when no
  n-gram matches (the slot falls back to the plain fused decode step).

**Token-identical acceptance.** Classic rejection sampling preserves
the output *distribution*; this engine makes the stronger claim — the
output *tokens* are byte-equal to the non-speculative engine, for
greedy AND sampled decoding. The verify program scores the k-token
draft chunk at k+1 positions and re-runs the request's OWN per-position
sampling: position i draws with exactly the PRNG split the
non-speculative chain would have used (``key_{t+i+1}, sk_{t+i} =
split(key_{t+i})``), and a draft token is accepted iff it EQUALS that
chain-sampled token (the token-identical specialization of rejection
sampling: acceptance probability is the indicator of the target's own
sample). The first mismatch position contributes the chain-sampled
token itself as the corrective emission, so every emitted token — and
every consumed PRNG split — is exactly what the non-speculative path
would have produced. Acceptance therefore only changes SPEED, never
tokens: adopt()/skip fast-forward, preemption replay, supervisor
rebuild and fleet migration all keep working unchanged (a speculative
engine can even adopt from a non-speculative one and vice versa).

**Paged rewind.** The verify program writes candidate K/V for all k+1
positions through the slot's block table (positions past the effective
draft width trash-redirect, the PR-8 masked-scatter machinery), then
the host rewinds the slot's ``cur`` to the accepted length. Rejected
lines sit beyond the causal bound (``view position <= cur``) and every
line is rewritten by the step that first exposes it, so rejected draft
KV is never readable; ``commit_prefix``/radix only ever index prompt
blocks, so unverified tokens can never be published for sharing.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

__all__ = ["SpecConfig"]

#: EngineMetrics counters the supervisor accumulates across rebuilds
#: (``EngineSupervisor.spec_totals``) so acceptance history survives an
#: engine incarnation being condemned.
SPEC_COUNTER_KEYS = ("spec_steps", "draft_steps", "spec_proposed_tokens",
                     "spec_accepted_tokens", "spec_emitted_tokens")


@dataclasses.dataclass
class SpecConfig:
    """Speculative-decoding configuration for ``Engine(speculative=...)``.

    ``draft`` is ``"ngram"`` (host-side n-gram lookahead over
    prompt+emitted tokens), a same-family CausalLM (model-draft), or any
    object with ``propose(ctx_ids, k) -> int32[<=k] | None`` (a custom
    host-side proposer — the chaos/worst-case test hook). ``k`` is the
    draft width: one verify pass scores k proposed tokens at k+1
    positions and emits between 1 and k+1 tokens. ``ngram_min`` /
    ``ngram_max`` bound the suffix order the n-gram proposer matches
    (longest first)."""

    draft: object = "ngram"
    k: int = 4
    ngram_max: int = 3
    ngram_min: int = 2

    def __post_init__(self):
        if int(self.k) < 1:
            raise ValueError("SpecConfig.k must be >= 1")
        self.k = int(self.k)
        if self.draft == "ngram":
            if not (1 <= int(self.ngram_min) <= int(self.ngram_max)):
                raise ValueError(
                    "need 1 <= ngram_min <= ngram_max")
            self.ngram_min = int(self.ngram_min)
            self.ngram_max = int(self.ngram_max)

    def draft_kind(self):
        if self.draft == "ngram":
            return "ngram"
        if hasattr(self.draft, "propose"):
            return "custom"
        return "model"


class _NgramState:
    """Per-handle incremental n-gram index: for each order n, the
    position AFTER the most recent occurrence of every n-gram ending at
    an already-continued position. Append-only (a request's context only
    grows, and replay/adopt rebuilds the same prefix), so indexing work
    is O(new tokens x orders) per proposal."""

    __slots__ = ("idx", "upto")

    def __init__(self, nmin, nmax):
        self.idx = {n: {} for n in range(nmin, nmax + 1)}
        self.upto = 0     # n-grams ending before this position are indexed


class NgramProposer:
    """Draft-free lookahead: propose the tokens that followed the most
    recent earlier occurrence of the context's current suffix (longest
    matching order first). Entirely host-side — no draft model, no extra
    XLA programs; a slot with no match falls back to the plain fused
    decode step for that iteration."""

    def __init__(self, cfg: SpecConfig):
        self.nmin = cfg.ngram_min
        self.nmax = cfg.ngram_max

    def propose(self, h, k_cap):
        ctx = h.prompt_ids.tolist() + h.tokens
        L = len(ctx)
        st = getattr(h, "_spec_ngram", None)
        if st is None:
            st = h._spec_ngram = _NgramState(self.nmin, self.nmax)
        # index n-grams ending at positions [upto, L-2]: each has a
        # known continuation at the next position
        for e in range(st.upto, L - 1):
            for n in range(self.nmin, self.nmax + 1):
                if e - n + 1 < 0:
                    continue
                st.idx[n][tuple(ctx[e - n + 1:e + 1])] = e + 1
        st.upto = max(st.upto, L - 1)
        for n in range(min(self.nmax, L - 1), self.nmin - 1, -1):
            pos = st.idx[n].get(tuple(ctx[L - n:]))
            if pos is not None:
                out = ctx[pos:pos + k_cap]
                if out:
                    return np.asarray(out, np.int32)
        return None


class _ModelDraft:
    """Same-family small-model draft with a paged pool of its own,
    tracking the target engine's slots one-for-one: slot ``s`` owns the
    fixed run of ``mb = ceil(max_len / block_size)`` blocks after the
    trash block, ``1 + s*mb .. (s+1)*mb`` — an identity block table, no
    allocator and no radix. The draft runs GREEDY: acceptance compares
    proposals against the target's chain-sampled tokens, so draft
    sampling would only add noise. Draft programs are the engine's
    module-level paged prefill and decode jits (with the draft's own
    weight shapes — they count toward the compile budget as
    ``draft_buckets_seen`` + one draft decode program)."""

    def __init__(self, engine, model):
        from .engine import _make_arch
        w, hp, geo = _make_arch(model)
        if hp["arch"] != engine._hp["arch"]:
            raise ValueError(
                f"draft model arch {hp['arch']!r} != target arch "
                f"{engine._hp['arch']!r}: speculative drafts must be "
                "same-family")
        if int(w["head"].shape[-1]) != engine._vocab:
            raise ValueError(
                f"draft vocab {int(w['head'].shape[-1])} != target "
                f"vocab {engine._vocab}")
        if engine.max_len > geo["max_pos"] and hp["arch"] == "gpt":
            raise ValueError("draft position table < engine max_len")
        self.engine = engine
        self._w = w
        # greedy statics: the draft's sampled path is never used
        self._statics = dict(hp, do_sample=False, top_k=0, top_p=None,
                             block_size=engine.block_size)
        S, bs, mb = engine.n_slots, engine.block_size, engine.cache.max_blocks
        self.tables = (1 + mb * np.arange(S)[:, None]
                       + np.arange(mb)[None]).astype(np.int32)
        shape = (geo["n_layers"], 1 + S * mb, bs) + geo["line"]
        self.kc = np.zeros(shape, geo["dtype"])
        self.vc = np.zeros(shape, geo["dtype"])
        self.tok = np.zeros(S, np.int32)
        self.cur = np.zeros(S, np.int32)
        self.keys = np.zeros((S, 2), np.uint32)
        self.temps = np.ones(S, np.float32)

    @staticmethod
    def _host(a):
        a = np.asarray(a)
        return a if a.flags.writeable else a.copy()

    def _programs(self):
        from . import engine as E
        if self.engine._donate:
            return E._PAGED_PREFILL_DONATED, E._PAGED_DECODE_DONATED
        return E._PAGED_PREFILL, E._PAGED_DECODE

    def on_admit(self, h, full):
        """Prefill the draft's KV for the slot's full token history
        (prompt + replayed tokens) — the admission/replay counterpart of
        the target prefill. The draft then chains from the TARGET's
        sampled token, not its own first guess."""
        from ..observability.compile_attr import compile_scope
        eng = self.engine
        slot, n_eff = h.slot, len(full)
        Lb = eng._bucket(n_eff)
        eng.draft_buckets_seen.add(Lb)
        ids = np.zeros((1, Lb), np.int32)
        ids[0, :n_eff] = full
        prefill, _ = self._programs()
        called = time.perf_counter()
        with compile_scope(f"spec.draft:L{Lb}"):
            out = eng._run_program(
                "draft_prefill", ("draft_prefill", Lb), prefill,
                (self._w, self.kc, self.vc, self.tok, self.cur,
                 self.keys, ids, np.int32(n_eff), np.int32(slot),
                 np.uint32(0), np.int32(0), np.float32(1.0),
                 self.tables[slot].copy(), np.int32(0),
                 eng._vmask[slot].copy()),
                self._statics, f"spec.draft:L{Lb}")
        self.kc, self.vc, tok, self.cur, self.keys, _ = out
        dispatched = time.perf_counter()
        tok = self._host(tok)
        eng._launched(f"spec.draft:L{Lb}", called, dispatched,
                      time.perf_counter(), "spec.draft_prefill", h,
                      tokens=n_eff, bucket=Lb)
        tok[slot] = h.tokens[-1]
        self.tok = tok

    def propose_all(self, cand):
        """k+1 fused greedy draft decode steps over every
        verify-eligible slot at once; the first k outputs are the
        proposals (the extra step writes the k-th proposal's KV so a
        full accept leaves no draft-cache hole)."""
        from ..observability.compile_attr import compile_scope
        eng = self.engine
        if not cand:
            return {}
        # the line each row writes at the first step; a row sits out the
        # steps that would write past ``max_len`` (its run of blocks ends
        # there, and the engine takes no proposal that far: ``k_cap``)
        pos = np.full(eng.n_slots, eng.max_len, np.int64)
        slots = [h.slot for h, _ in cand]
        pos[slots] = np.asarray(self.cur)[slots]
        _, decode = self._programs()
        outs = {h.slot: [] for h, _ in cand}
        k = eng.spec.k
        with compile_scope("spec.draft"):
            for i in range(k + 1):
                called = time.perf_counter()
                out = eng._run_program(
                    "draft_decode", ("draft_decode",), decode,
                    (self._w, self.kc, self.vc, self.tables.copy(),
                     self.tok, self.cur, pos + i < eng.max_len,
                     self.keys, self.temps, eng._vmask.copy()),
                    self._statics, "spec.draft")
                nxt, self.kc, self.vc, self.cur, self.keys = out
                self.tok = nxt
                dispatched = time.perf_counter()
                toks = np.asarray(nxt)
                eng._launched("spec.draft", called, dispatched,
                              time.perf_counter(), "spec.draft",
                              tokens=len(cand), draft_step=i, k=k)
                for h, _ in cand:
                    outs[h.slot].append(int(toks[h.slot]))
                eng.metrics.draft_steps += 1
        eng.draft_decode_used = True
        return {slot: np.asarray(v[:k], np.int32)
                for slot, v in outs.items()}

    def after_verify(self, h, last_tok, new_cur):
        """Rewind/advance the draft to the target's post-verify state:
        tok = the last emitted (chain-sampled) token, cur = the accepted
        length. Draft lines beyond sit past the causal bound and are
        rewritten before they are ever attendable — the same stale-line
        argument as slot reuse."""
        slot = h.slot
        tok = self._host(self.tok)
        cur = self._host(self.cur)
        tok[slot] = last_tok
        cur[slot] = new_cur
        self.tok, self.cur = tok, cur

    def probe_specs(self, buckets):
        """(kind, hkey, jitted, abstract args, statics, origin) probes
        for the draft program set — precompile_aot coverage mirroring
        the live draft call sites operand for operand."""
        import jax
        eng = self.engine

        def sds(a):
            return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

        w = {k: sds(v) for k, v in self._w.items()}
        kc, vc = sds(self.kc), sds(self.vc)
        S = eng.n_slots
        tok = jax.ShapeDtypeStruct((S,), np.int32)
        cur = jax.ShapeDtypeStruct((S,), np.int32)
        keys = jax.ShapeDtypeStruct((S, 2), np.uint32)
        temps = jax.ShapeDtypeStruct((S,), np.float32)
        act = jax.ShapeDtypeStruct((S,), np.bool_)
        vm = jax.ShapeDtypeStruct((S, eng._vocab), np.float32)
        i32 = jax.ShapeDtypeStruct((), np.int32)
        u32 = jax.ShapeDtypeStruct((), np.uint32)
        f32 = jax.ShapeDtypeStruct((), np.float32)
        vrow = jax.ShapeDtypeStruct((eng._vocab,), np.float32)
        trow = sds(self.tables[0])
        prefill, decode = self._programs()
        specs = []
        for Lb in buckets:
            ids = jax.ShapeDtypeStruct((1, int(Lb)), np.int32)
            specs.append((
                "draft_prefill", ("draft_prefill", int(Lb)), prefill,
                (w, kc, vc, tok, cur, keys, ids, i32, i32, u32, i32, f32,
                 trow, i32, vrow),
                self._statics, f"spec.draft:L{Lb}"))
        specs.append((
            "draft_decode", ("draft_decode",), decode,
            (w, kc, vc, sds(self.tables), tok, cur, act, keys, temps, vm),
            self._statics, "spec.draft"))
        return specs


class _HostProposerAdapter:
    """Wrap a custom ``propose(ctx_ids, k) -> tokens|None`` object (or
    the built-in NgramProposer, which takes the handle directly)."""

    def __init__(self, proposer, by_handle):
        self.proposer = proposer
        self.by_handle = by_handle

    def on_admit(self, h, full):
        pass

    def after_verify(self, h, last_tok, new_cur):
        pass

    def propose_all(self, cand):
        out = {}
        for h, k_cap in cand:
            if self.by_handle:
                p = self.proposer.propose(h, k_cap)
            else:
                ctx = np.concatenate(
                    [h.prompt_ids, np.asarray(h.tokens, np.int32)])
                p = self.proposer.propose(ctx, k_cap)
            if p is not None and len(p):
                out[h.slot] = np.asarray(p[:k_cap], np.int32)
        return out

    def probe_specs(self, buckets):
        return []


def make_runtime(engine, cfg: SpecConfig, model=None):
    """Build the draft runtime for an engine: NgramProposer ("ngram"),
    a custom host proposer (``propose`` protocol), or a model draft."""
    kind = cfg.draft_kind()
    if kind == "ngram":
        return _HostProposerAdapter(NgramProposer(cfg), by_handle=True)
    if kind == "custom":
        return _HostProposerAdapter(cfg.draft, by_handle=False)
    return _ModelDraft(engine, cfg.draft)

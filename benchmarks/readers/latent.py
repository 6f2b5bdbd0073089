"""Per-layer metrics of a latent-attention model that holds a share of its
routed experts (a configuration with ``kv_lora_rank``): from the device
trace of a traced run and from the program's counters
(``Engine.stats()["latent"]`` and ``["moe"]``, which the serving driver
keeps as ``run.samples["engine_stats"]``).

Every reader returns ``None`` for any other configuration, for a program
that keeps no such counters, and, for the device metrics, without a device
trace.

**Every run of a program, not decode-only steps.** Under this model's
traffic (long prompts, short answers) every ``Engine.step()`` may carry a
chunk, and then no step only decodes. The decode program still runs once a
step and the chunk program beside it: their runs are found on the trace's
``XLA Modules`` line by the program's name, and the device metrics are of
the operations inside those runs, a run. Which operations are which is
decided by what an operation IS, never by a fusion's number: the decode
kernel by its own name (``paged_latent_attention``), the routed share's
fusions by the held banks', the router's and the shared expert's
dimensions in their text (``[held,h,f]``, ``[held,f,h]``, ``[h,scored]``,
``[h,f]``, ``[f,h]``), the chunk's attention, which is XLA's fusions, by
the layout of its scores and of the cached lines it expands (``[heads,
chunk rows, ...]`` and ``[..., heads, nope + v]``).

**Two windows**, as in ``readers/routed.py``: the counters are cumulative
over the engine's life (check sample, warm-up, window) and taken once,
when the window closes; the device times are of the traced window's decode
calls. Both are means over decode calls beside chunks, where fewer rows
decode than in a step that only decodes; the check's sample decodes four
rows a call, so the life-long mean of lines and experts lies under the
traced window's: the bytes are counted low, and both shares read low
rather than high.
"""
from __future__ import annotations

from benchmarks import arithmetic, arithmetic_latent, trace_reduce
from benchmarks.readers import routed

KERNEL = "paged_latent_attention"


def _stats(run):
    if "kv_lora_rank" not in run.config:
        return None
    stats = run.samples.get("engine_stats", {})
    latent, moe = stats.get("latent"), stats.get("moe")
    if not latent or not latent.get("decode_calls") or not moe \
            or not moe.get("decode_calls"):
        return None
    return latent, moe


def held_expert_imbalance(run):
    """The busiest held expert's picks over the mean held expert's, of the
    layer where that is largest, over the engine's life. Beside it the
    share of all picks that landed on held experts, which should read
    held / scored."""
    found = _stats(run)
    if found is None:
        return None
    moe = found[1]
    if "picks" in moe:
        run.info("held_picks", picks=moe["picks"],
                 picks_held=moe["picks_held"],
                 share=moe["picks_held"] / max(moe["picks"], 1),
                 expected_share=run.config["n_routed_experts"]
                 / run.config.get("n_router_experts",
                                  run.config["n_routed_experts"]))
    return routed.imbalance(moe["expert_tokens"])


def marks(config):
    """The texts that name the routed share's own arrays."""
    e, h, f = (config["n_routed_experts"], config["hidden_size"],
               config["moe_intermediate_size"])
    scored = config.get("n_router_experts", e)
    fs = f * config["n_shared_experts"]
    return (f"[{e},{h},{f}]", f"[{e},{f},{h}]", f"[{h},{scored}]",
            f"[{h},{fs}]", f"[{fs},{h}]")


MODULES_LINE = "XLA Modules"
DECODE_PROGRAM, CHUNK_PROGRAM = "paged_decode", "paged_chunk"


def chunk_marks(run):
    """The texts that name the arrays of a chunk's attention: whatever is
    laid out heads x chunk rows x ... (the queries, the float32 scores
    against a tile of cached lines, their sum under the probabilities),
    and the lines that ``kv_b_proj`` expands, ... x heads x (nope + v)."""
    heads, rows = (run.config["num_attention_heads"],
                   run.traffic.get("engine", {}).get("prefill_chunk"))
    if not rows:
        return None
    wide = run.config["qk_nope_head_dim"] + run.config["v_head_dim"]
    return (f"[{heads},{rows},", f",{heads},{wide}]")


def program_runs(events, lo, hi, program):
    """``[(start, end)]`` of the runs of ``program`` on the first device,
    wholly inside ``[lo, hi]`` of trace time."""
    planes = sorted({p for p, *_ in events
                     if p.startswith(trace_reduce.DEVICE_PLANE)})
    return sorted((s, s + d) for p, line, name, s, d in events
                  if planes and p == planes[0] and line == MODULES_LINE
                  and program in name and s >= lo and s + d <= hi)


def _program_split(run, program, texts):
    """``(ms of the operations whose text holds one of texts[i], ...,
    ms of the rest, ms of a run, runs, [by operation, ...])`` a run of
    ``program`` on the first device, or ``None``."""
    t = run.trace if run.trace and run.trace["reduced"] else None
    if t is None:
        return None
    lo, hi = trace_reduce.window_of(t["events"])
    runs = program_runs(t["events"], lo, hi, program)
    planes = trace_reduce.device_ops(t["events"])
    if not runs or not planes:
        return None
    ops = trace_reduce.self_times(planes[sorted(planes)[0]])
    found = [routed.split_steps(ops, runs, marks) for marks in texts]
    mine = [f[0] * 1e-6 for f in found]
    everything = (found[0][0] + found[0][1]) * 1e-6
    if max(mine) <= 0:
        return None
    program_ms = sum(e - s for s, e in runs) / len(runs) * 1e-6
    by_operation = [{k: v * 1e-6 for k, v in sorted(
        f[2].items(), key=lambda kv: -kv[1])} for f in found]
    return mine, everything - sum(mine), program_ms, len(runs), by_operation


def _split(run):
    """Computed once a run: ``(kernel_ms, routed_ms, program_ms)`` a run
    of the decode program on the first device, or ``None``."""
    if hasattr(run, "latent_split"):
        return run.latent_split
    run.latent_split = None
    found = _program_split(run, DECODE_PROGRAM,
                           [(KERNEL,), marks(run.config)]) \
        if "kv_lora_rank" in run.config else None
    if found is None:
        return None
    (kernel, share), rest, program, runs, by_operation = found
    run.info("latent_ops", decode_runs=runs, program_ms=program,
             kernel_ms=kernel, routed_share_ms=share, rest_ms=rest,
             operations_ms=kernel + share + rest,
             routed_share_by_operation_ms=by_operation[1])
    run.latent_split = (kernel, share, program)
    return run.latent_split


def _chunk_split(run):
    """Computed once a run: ``(attention_ms, routed_ms, program_ms)`` a
    run of the chunk program on the first device, or ``None``."""
    if hasattr(run, "latent_chunk_split"):
        return run.latent_chunk_split
    run.latent_chunk_split = None
    texts = chunk_marks(run) if "kv_lora_rank" in run.config else None
    found = texts and _program_split(run, CHUNK_PROGRAM,
                                     [texts, marks(run.config)])
    if not found:
        return None
    (attention, share), rest, program, runs, by_operation = found
    run.info("latent_chunk_ops", chunk_runs=runs, program_ms=program,
             attention_ms=attention, routed_share_ms=share, rest_ms=rest,
             operations_ms=attention + share + rest,
             attention_by_operation_ms=by_operation[0],
             routed_share_by_operation_ms=by_operation[1])
    run.latent_chunk_split = (attention, share, program)
    return run.latent_chunk_split


def chunk_latent_attention_device_ms(run):
    """Device self time, a run of the chunk program, of its attention:
    the operations over the scores of the chunk's rows against the cached
    lines, tile by tile, and over the lines' expansion by ``kv_b_proj``."""
    split = _chunk_split(run)
    return split[0] if split and split[0] > 0 else None


def chunk_routed_share_device_ms(run):
    """Device self time, a run of the chunk program, of the operations
    that read the held banks, the router or the shared expert: every held
    expert applied to every row of the chunk."""
    split = _chunk_split(run)
    return split[1] if split and split[1] > 0 else None


def latent_attention_device_ms(run):
    """Device self time of the decode kernel's calls (one a layer) in a
    run of the decode program."""
    split = _split(run)
    return split[0] if split and split[0] > 0 else None


def routed_share_device_ms(run):
    """Device self time, a run of the decode program, of the operations
    that read the held banks, the router or the shared expert."""
    split = _split(run)
    return split[1] if split and split[1] > 0 else None


def latent_attention_roofline_pct(run):
    """The least time the decode kernels of a step can take for the lines
    its rows could see (``arithmetic_latent.kernel_seconds``: the larger
    of bytes over bandwidth and operations over the bf16 peak, the lines at
    their published 1152 B) over ``latent_attention_device_ms``."""
    ms, found = latent_attention_device_ms(run), _stats(run)
    if ms is None or found is None:
        return None
    latent = found[0]
    lines = latent["lines"] / latent["decode_calls"]
    least_s = arithmetic_latent.kernel_seconds(
        run.config, lines, arithmetic.peaks(run.device_kind))
    run.info("latent_kernel", lines_seen_a_step=lines,
             line_bytes=arithmetic_latent.line_bytes(run.config),
             program_line_bytes=latent["line_bytes"], least_ms=least_s * 1e3)
    return 100.0 * least_s / (ms * 1e-3)


def latent_decode_hbm_roofline_pct(run):
    """The bytes a decode call cannot avoid
    (``arithmetic_latent.decode_step_bytes``) over the device time of a
    run of the decode program at the chip's memory bandwidth. Names no
    kernel: it reads the same work whatever implements the layers."""
    split, found = _split(run), _stats(run)
    if split is None or found is None:
        return None
    latent, moe = found
    lines = latent["lines"] / latent["decode_calls"]
    hit = sum(moe["experts_hit"]) / moe["decode_calls"]
    total = arithmetic_latent.decode_step_bytes(run.config, lines, hit)
    least_s = total / arithmetic.peaks(run.device_kind)["hbm_bytes_per_s"]
    run.info("latent_decode_step_bytes", bytes_a_step=total,
             weight_bytes=arithmetic_latent.weight_bytes(run.config, hit),
             lines_seen_a_step=lines, held_experts_hit_a_step=hit,
             least_ms=least_s * 1e3, decode_program_ms=split[2])
    return 100.0 * least_s / (split[2] * 1e-3)

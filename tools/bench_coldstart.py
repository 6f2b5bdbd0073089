#!/usr/bin/env python
"""Process-restart cold-start A/B for the paddle_tpu.aot executable
cache (ROADMAP item 4's headline number).

Measures, with subprocess pairs so every arm pays a REAL process start:

* **eager** — wall of the first MLP+Adam train step and backend compile
  count over a short loop, for (cache off) vs (cold cache) vs (warm
  cache, same dir). The warm arm must compile NOTHING and reproduce the
  cache-off losses bitwise.
* **serving** — ``create_llm_predictor`` build wall, time-to-first-token
  and serving-path compile count for an artifact saved WITHOUT
  precompiled programs vs WITH them (``save_lm(precompile=True)``).
  The precompiled arm must serve its first token with 0 XLA backend
  compiles and token-identical output.

Emits one JSON ledger line; ``ok`` gates the zero-compile + bitwise
claims. Stand-alone: every arm is a child process and this parent never
imports jax, so the children — one at a time — are each the only jax
process (a parent that has touched jax owns the chip, and its children
cannot have it). Runs on the CPU unless JAX_PLATFORMS says otherwise.

    JAX_PLATFORMS=cpu python tools/bench_coldstart.py [--json]
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_EAGER_CHILD = r"""
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
t_proc = time.perf_counter()
import paddle_tpu as paddle
from paddle_tpu import analysis

paddle.seed(0)
rng = np.random.default_rng(0)
x = paddle.to_tensor(rng.standard_normal((32, 64)).astype(np.float32))
y = paddle.to_tensor(rng.integers(0, 10, (32,)).astype(np.int64))
net = paddle.nn.Sequential(paddle.nn.Linear(64, 64), paddle.nn.ReLU(),
                           paddle.nn.Linear(64, 10))
opt = paddle.optimizer.Adam(learning_rate=1e-3,
                            parameters=net.parameters())
counter = analysis.CompileEventCounter().install()
counter.reset()
losses = []
t0 = time.perf_counter()
first = None
for i in range(6):
    loss = paddle.nn.functional.cross_entropy(net(x), y)
    loss.backward()
    opt.step()
    opt.clear_grad()
    losses.append(float(loss.numpy()))
    if first is None:
        first = time.perf_counter() - t0
print(json.dumps({
    "first_step_s": round(first, 4),
    "loop_s": round(time.perf_counter() - t0, 4),
    "setup_s": round(t0 - t_proc, 4),
    "workload_compiles": counter.count if counter.available else None,
    "loss_bits": [np.float32(v).tobytes().hex() for v in losses]}))
"""

_SAVE_LM_CHILD = r"""
import dataclasses, json, os, sys
sys.path.insert(0, %(repo)r)
import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.text.models.llama import LLAMA_TINY, LlamaForCausalLM

plain, pre = sys.argv[1:3]
cfg = dataclasses.replace(LLAMA_TINY, dtype="float32", num_hidden_layers=2)
paddle.seed(0)
model = LlamaForCausalLM(cfg)
model.eval()
serving.save_lm(model, plain, precompile=False)
serving.save_lm(model, pre, precompile=True, n_slots=2, max_len=64,
                min_prompt_bucket=8)
print(json.dumps({"saved": [plain, pre]}))
"""

_SERVING_CHILD = r"""
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.inference import create_llm_predictor

art = sys.argv[1]
counter = analysis.CompileEventCounter().install()
t0 = time.perf_counter()
pred = create_llm_predictor(art)
build_s = time.perf_counter() - t0
counter.reset()          # serving window: engine programs + sampling
ttft = [None]
t1 = time.perf_counter()
h = pred.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=8,
                on_token=lambda h, t: ttft.__setitem__(
                    0, ttft[0] or time.perf_counter() - t1))
toks = h.result()
print(json.dumps({
    "predictor_build_s": round(build_s, 4),
    "ttft_s": round(ttft[0], 4),
    "serve_s": round(time.perf_counter() - t1, 4),
    "serving_compiles": counter.count if counter.available else None,
    "tokens": np.asarray(toks).tolist(),
    "sources": pred.engine.aot_stats()}))
"""


def _child(code, env_extra=None, argv=()):
    env = dict(os.environ, **(env_extra or {}))
    t0 = time.perf_counter()
    out = subprocess.run([sys.executable, "-c", code, *argv],
                         capture_output=True, text=True, env=env)
    wall = time.perf_counter() - t0
    if not out.stdout.strip():
        return {"error": out.stderr[-800:], "process_wall_s": round(wall, 3)}
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    rec["process_wall_s"] = round(wall, 3)
    return rec


def bench_eager_coldstart():
    code = _EAGER_CHILD % {"repo": REPO}
    cache_dir = tempfile.mkdtemp(prefix="aot-coldstart-")
    base = {"PADDLE_TPU_EAGER_CACHE_WARMUP": "1",
            "PADDLE_TPU_FUSED_STEP_WARMUP": "0"}
    off = _child(code, {**base, "PADDLE_TPU_AOT_CACHE": "0"})
    cold = _child(code, {**base, "PADDLE_TPU_AOT_CACHE_DIR": cache_dir})
    warm = _child(code, {**base, "PADDLE_TPU_AOT_CACHE_DIR": cache_dir})
    ok = ("error" not in off and "error" not in warm
          and warm.get("workload_compiles") == 0
          and warm.get("loss_bits") == off.get("loss_bits")
          and cold.get("loss_bits") == off.get("loss_bits"))
    speedup = None
    if ok and warm.get("first_step_s"):
        speedup = round(off["first_step_s"] / warm["first_step_s"], 2)
    return {"cache_dir": cache_dir, "off": off, "cold": cold,
            "warm": warm, "first_step_speedup": speedup,
            "bitwise_equal": warm.get("loss_bits") == off.get("loss_bits"),
            "ok": ok}


def bench_serving_coldstart():
    tmp = tempfile.mkdtemp(prefix="aot-coldstart-lm-")
    plain = os.path.join(tmp, "lm_plain")
    pre = os.path.join(tmp, "lm_pre")
    saved = _child(_SAVE_LM_CHILD % {"repo": REPO}, argv=(plain, pre))
    if "error" in saved:
        raise RuntimeError(f"save_lm child failed: {saved['error']}")
    code = _SERVING_CHILD % {"repo": REPO}
    # the plain arm gets the same geometry explicitly so the ONLY delta
    # is the precompiled program set
    off = _child(code, argv=(plain,))
    warm = _child(code, argv=(pre,))
    ok = ("error" not in off and "error" not in warm
          and warm.get("serving_compiles") == 0
          and warm.get("tokens") == off.get("tokens"))
    speedup = None
    if ok and warm.get("ttft_s"):
        speedup = round(off["ttft_s"] / warm["ttft_s"], 2)
    return {"artifacts": tmp, "off": off, "warm": warm,
            "ttft_speedup": speedup,
            "token_identical": warm.get("tokens") == off.get("tokens"),
            "ok": ok}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--arm", choices=("eager", "serving", "both"),
                    default="both")
    args = ap.parse_args()
    record = {"bench": "coldstart",
              "jax_platforms": os.environ["JAX_PLATFORMS"]}
    if args.arm in ("eager", "both"):
        record["eager"] = bench_eager_coldstart()
    if args.arm in ("serving", "both"):
        record["serving"] = bench_serving_coldstart()
    record["ok"] = all(record[k]["ok"] for k in ("eager", "serving")
                       if k in record)
    if args.json:
        print(json.dumps(record))
    else:
        if "eager" in record:
            e = record["eager"]
            print(f"eager  first-step {e['off'].get('first_step_s')}s off "
                  f"-> {e['warm'].get('first_step_s')}s warm "
                  f"({e['first_step_speedup']}x), warm compiles "
                  f"{e['warm'].get('workload_compiles')}")
        if "serving" in record:
            s = record["serving"]
            print(f"serve  TTFT {s['off'].get('ttft_s')}s plain -> "
                  f"{s['warm'].get('ttft_s')}s precompiled "
                  f"({s['ttft_speedup']}x), warm compiles "
                  f"{s['warm'].get('serving_compiles')}")
        print("OK" if record["ok"] else "FAIL")
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""Whole-program jitted Executor for TRAINING programs.

Reference: fluid/executor.py — the 1.x idiom is `opt.minimize(loss)` once
at build, then `exe.run(feed, fetch_list=[loss])` in a loop; the C++
executor runs the whole ProgramDesc (forward + grad ops + optimizer ops)
fused. The TPU-native analog (static/program.py::_build_replay_plan)
compiles that loop body into ONE jax.jit program per (program, feed
signature, fetch set): jax.grad re-derives the backward inside the trace,
the optimizer's pure update_param fuses the step, While/Switch lower to
lax control flow, and parameter/moment buffers are DONATED so the update
is copy-free.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu import nn, static
from paddle_tpu import optimizer as optim
from paddle_tpu.fluid import layers


def _make_regression(n=64, d=4, seed=1):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(d, 1)).astype(np.float32)
    return xs, (xs @ w).astype(np.float32)


def _build_train_program(opt_factory, depth=2, width=8):
    paddle.seed(0)
    main = static.Program()
    with static.program_guard(main):
        x = static.data('x', [None, 4], 'float32')
        yt = static.data('y', [None, 1], 'float32')
        h = x
        params = []
        for _ in range(depth):
            layer = nn.Linear(int(h.shape[-1]), width)
            params += layer.parameters()
            h = paddle.nn.functional.relu(layer(h))
        head = nn.Linear(width, 1)
        params += head.parameters()
        loss = ((head(h) - yt) ** 2).mean()
        opt = opt_factory(params)
        opt.minimize(loss)
    return main, loss


def _run_steps(main, loss, xs, ys, steps):
    exe = static.Executor()
    out = []
    for _ in range(steps):
        lv, = exe.run(main, feed={'x': xs, 'y': ys}, fetch_list=[loss])
        out.append(float(lv))
    return out


def _the_plan(prog):
    plans = [p for p in prog._jit_cache.values() if p is not None]
    assert plans, "train program did not take the compiled path"
    return plans[0]


class TestCompiledTrainLoop:
    def test_minimize_loop_matches_eager(self):
        """(a) the classic fluid loop: minimize + repeated exe.run.
        The first fetched loss (pure forward, fresh params) must match
        the eager op-by-op replay bitwise; post-update losses may drift
        by fusion ULPs only."""
        xs, ys = _make_regression()

        def sgd(params):
            return fluid.optimizer.SGDOptimizer(
                learning_rate=0.1, parameter_list=params)

        main, loss = _build_train_program(sgd)
        jit_losses = _run_steps(main, loss, xs, ys, 5)
        os.environ['PADDLE_TPU_STATIC_JIT'] = '0'
        try:
            main2, loss2 = _build_train_program(sgd)
            eager_losses = _run_steps(main2, loss2, xs, ys, 5)
        finally:
            del os.environ['PADDLE_TPU_STATIC_JIT']
        assert jit_losses[0] == eager_losses[0], \
            (jit_losses[0], eager_losses[0])
        np.testing.assert_allclose(jit_losses, eager_losses,
                                   rtol=1e-5, atol=1e-7)
        assert jit_losses[-1] < jit_losses[0]

    def test_compiled_path_taken_and_cached(self):
        """(b) one build, then cache hits: the plan's call counter moves
        once per exe.run and no host entries leak into the plan."""
        xs, ys = _make_regression()
        main, loss = _build_train_program(
            lambda ps: optim.SGD(learning_rate=0.1, parameters=ps))
        _run_steps(main, loss, xs, ys, 4)
        plan = _the_plan(main)
        # first sighting runs eager (compile defers until the key
        # repeats), every later step goes through the plan
        assert plan.calls == 3
        assert plan.n_host == 0
        assert len(plan.segments) == 1  # whole program, single callable
        assert len(main._jit_cache) == 1  # one key: no rebuild per step

    def test_adam_moments_thread_through_compiled_state(self):
        """Adam's moments live in the donated state, not re-read from
        zero: the compiled loop must converge like eager (values drift
        by float-fusion ULPs, trajectories must stay close)."""
        xs, ys = _make_regression()

        def adam(params):
            return optim.Adam(learning_rate=0.05, parameters=params)

        main, loss = _build_train_program(adam)
        jit_losses = _run_steps(main, loss, xs, ys, 10)
        os.environ['PADDLE_TPU_STATIC_JIT'] = '0'
        try:
            main2, loss2 = _build_train_program(adam)
            eager_losses = _run_steps(main2, loss2, xs, ys, 10)
        finally:
            del os.environ['PADDLE_TPU_STATIC_JIT']
        np.testing.assert_allclose(jit_losses, eager_losses,
                                   rtol=1e-4, atol=1e-6)
        plan = _the_plan(main)
        seg = plan.segments[0]
        # params + moment1/moment2/beta1_pow/beta2_pow per param
        kinds = [s[0] for s in seg.state_specs]
        assert kinds.count("opt") == 4 * kinds.count("param")

    def test_while_training_program_compiles_single_callable(self):
        """(c) a Program containing While AND minimize executes via one
        jitted callable — no per-op eager dispatch."""
        paddle.seed(0)
        main = static.Program()
        with static.program_guard(main):
            x = static.data('x', [None, 4], 'float32')
            yt = static.data('y', [None, 1], 'float32')
            layer = nn.Linear(4, 1)
            base = ((layer(x) - yt) ** 2).mean()
            # While computes a loop-carried scale (grad-free host-style
            # counter loop — the 1.x warmup/readjust idiom)
            lim = layers.fill_constant([1], 'float32', 3.0)
            i = layers.fill_constant([1], 'float32', 0.0)
            cond = layers.less_than(i, lim)
            w = layers.While(cond)
            with w.block():
                layers.increment(i, value=1.0)
                layers.less_than(i, lim, cond=cond)
            scale = layers.elementwise_add(
                i, layers.fill_constant([1], 'float32', 0.0))
            scale.stop_gradient = True
            loss = base * scale
            opt = optim.SGD(learning_rate=0.02,
                            parameters=layer.parameters())
            opt.minimize(loss)
        xs, ys = _make_regression(n=16)
        jit_losses = _run_steps(main, loss, xs, ys, 3)
        plan = _the_plan(main)
        assert plan.calls == 2 and plan.n_host == 0 \
            and len(plan.segments) == 1
        kinds = [e[0] for e in main._ops]
        assert "while" in kinds and "minimize" in kinds
        os.environ['PADDLE_TPU_STATIC_JIT'] = '0'
        try:
            eager_losses = _run_steps(main, loss, xs, ys, 3)
        finally:
            del os.environ['PADDLE_TPU_STATIC_JIT']
        # the compiled runs already advanced the params; eager continues
        # the SAME trajectory, so losses keep decreasing smoothly
        assert eager_losses[0] < jit_losses[-1]

    def test_append_backward_grads_compiled(self):
        """append_backward programs compile too: fetched grad holders
        come from jax.grad inside the trace and match the closed form."""
        main = static.Program()
        with static.program_guard(main):
            x = static.data('x', [None, 3], 'float32')
            w = static.create_parameter([3, 1], 'float32')
            w.stop_gradient = False
            loss = x.matmul(w).sum()
            grads = static.append_backward(loss, parameter_list=[w])
        exe = static.Executor()
        feed = np.ones((5, 3), dtype=np.float32)
        for _ in range(2):
            _, g = exe.run(main, feed={'x': feed},
                           fetch_list=[loss, grads[0][1]])
        np.testing.assert_allclose(g, 5 * np.ones((3, 1)), atol=1e-6)
        plan = _the_plan(main)
        assert plan.calls == 1 and plan.n_host == 0

    def test_param_and_moment_buffers_donated(self):
        """Parameter/moment buffers are donated into the compiled train
        step: the lowering carries input-output aliases AND the previous
        param buffer is actually invalidated after a step (no O(params)
        copy kept alive)."""
        xs, ys = _make_regression()
        main, loss = _build_train_program(
            lambda ps: optim.Adam(learning_rate=0.05, parameters=ps))
        _run_steps(main, loss, xs, ys, 2)  # eager step, then build+run
        plan = _the_plan(main)
        seg = plan.segments[0]
        assert seg.donated
        n_state = len(seg.state_specs)
        assert n_state > 0 and seg.alias_count >= n_state
        # live-buffer proof: the pre-step param buffer dies on donation
        param = next(s[1] for s in seg.state_specs if s[0] == "param")
        before = param._data
        _run_steps(main, loss, xs, ys, 1)
        assert param._data is not before
        assert before.is_deleted(), \
            "old param buffer still alive — donation did not happen"

    def test_host_entry_keeps_per_op_eager_fallback(self):
        """py_func host IO drops ONLY that entry to eager — the
        surrounding ops still run compiled (segmented plan)."""
        seen = []
        main = static.Program()
        with static.program_guard(main):
            x = static.data('x', [None, 2], 'float32')
            h = x * 2.0
            out_holder = paddle.Tensor(np.zeros((1,), np.float32))
            static.py_func(lambda t: (seen.append(1),
                                      np.asarray(t._data).sum())[1],
                           h, out_holder)
            y = h + 1.0
        exe = static.Executor()
        for _ in range(3):
            got, = exe.run(main, feed={'x': np.ones((2, 2), np.float32)},
                           fetch_list=[y])
        np.testing.assert_allclose(got, 3 * np.ones((2, 2)))
        plan = _the_plan(main)
        assert plan.n_host == 1 and len(plan.segments) == 2
        assert plan.calls == 2  # step 1 eager, steps 2-3 via the plan
        assert len(seen) == 3  # host thunk really ran every step


class TestSatelliteRegressions:
    def test_fetch_cache_key_uses_stable_tokens_not_id(self):
        """ADVICE #5: fetch Tensors key by a monotonic per-Tensor token;
        id() reuse after GC can never resurrect a stale cache verdict."""
        from paddle_tpu.static.program import _stable_token
        a = paddle.Tensor(np.zeros((1,), np.float32))
        tok_a = _stable_token(a)
        assert _stable_token(a) == tok_a  # stable across calls
        b = paddle.Tensor(np.zeros((1,), np.float32))
        assert _stable_token(b) != tok_a
        del a
        import gc
        gc.collect()
        c = paddle.Tensor(np.zeros((1,), np.float32))
        assert _stable_token(c) not in (tok_a, _stable_token(b))
        # and the cache key embeds the token, not id()
        main = static.Program()
        with static.program_guard(main):
            x = static.data('x', [None, 2], 'float32')
            y = x * 2.0
        exe = static.Executor()
        exe.run(main, feed={'x': np.ones((1, 2), np.float32)},
                fetch_list=[y])
        (key,) = main._jit_cache.keys()
        assert key[2] == (("#t", _stable_token(y)),)

    def test_kl_divergence_categorical_keepdims_shape(self):
        """ADVICE #1: module-level kl_divergence delegates to the method
        so Categorical keeps the reference [..., 1] contract."""
        from paddle_tpu.distribution import Categorical, kl_divergence
        logits_p = paddle.to_tensor(
            np.random.default_rng(0).normal(size=(5, 3)).astype(np.float32))
        logits_q = paddle.to_tensor(
            np.random.default_rng(1).normal(size=(5, 3)).astype(np.float32))
        p, q = Categorical(logits_p), Categorical(logits_q)
        out = kl_divergence(p, q)
        assert out.shape == [5, 1]
        np.testing.assert_allclose(np.asarray(out._data),
                                   np.asarray(p.kl_divergence(q)._data))

    def test_asp_masked_step_skips_when_step_owns_no_params(self):
        """ADVICE #2: a step exposing no params must NOT widen the mask
        reapply to every pruned model in the process."""
        from paddle_tpu.distributed.fleet import _ASPMaskedStep
        from paddle_tpu.static import sparsity

        calls = []
        orig = sparsity._reapply_masks
        sparsity._reapply_masks = lambda only_ids=None: calls.append(only_ids)
        try:
            class _Step:
                _params = {}

                def __call__(self):
                    return "ok"
            assert _ASPMaskedStep(_Step())() == "ok"
            assert calls == [], "empty step must skip the reapply entirely"

            class _Owner:
                def __call__(self):
                    return "ok"
            p = paddle.Parameter(np.ones((2, 2), np.float32))
            owner = _Owner()
            owner._params = {"w": p}
            _ASPMaskedStep(owner)()
            assert calls == [{id(p)}]  # scoped, never None
        finally:
            sparsity._reapply_masks = orig

    def test_global_scatter_gather_validate_counts_eager(self):
        """ADVICE #3: world_size-1 eager path raises on mismatched
        local/global counts instead of silently slicing wrong rows."""
        from paddle_tpu.distributed.utils import (global_gather,
                                                  global_scatter)
        x = paddle.to_tensor(np.ones((4, 2), np.float32))
        lc = paddle.to_tensor(np.asarray([2, 2], np.int64))
        gc_bad = paddle.to_tensor(np.asarray([1, 2], np.int64))
        gc_ok = paddle.to_tensor(np.asarray([3, 1], np.int64))
        with pytest.raises(ValueError, match="local_count.sum"):
            global_scatter(x, lc, gc_bad)
        with pytest.raises(ValueError, match="local_count.sum"):
            global_gather(x, lc, gc_bad)
        out = global_scatter(x, lc, gc_ok)
        assert out.shape == [4, 2]

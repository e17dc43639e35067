"""Timers + flops profiler (reference utils/timer.py, profiling/flops_profiler;
test pattern: tests/unit/profiling/flops_profiler/test_flops_profiler.py)."""

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

import deepspeed_tpu
from deepspeed_tpu.profiling.flops_profiler import (FlopsProfiler, flops_of,
                                                    get_model_profile)
from deepspeed_tpu.utils.timer import (NoopTimer, SynchronizedWallClockTimer,
                                       ThroughputTimer, trim_mean)

from simple_model import SimpleModel, random_batch


class TestTimers:
    def test_basic_elapsed(self):
        timers = SynchronizedWallClockTimer()
        t = timers("region")
        t.start()
        time.sleep(0.02)
        t.stop()
        elapsed = t.elapsed(reset=False)
        assert 10.0 < elapsed < 500.0  # msec

    def test_mean_and_reset(self):
        timers = SynchronizedWallClockTimer()
        t = timers("r")
        for _ in range(3):
            t.start()
            time.sleep(0.005)
            t.stop()
        assert len(t.elapsed_records) == 3
        assert t.mean() > 0
        t.reset()
        assert t.elapsed_records == []

    def test_log_returns_means(self):
        timers = SynchronizedWallClockTimer()
        t = timers("a")
        t.start()
        time.sleep(0.01)
        t.stop()
        means = timers.log(["a", "missing"])
        assert "a" in means and "missing" not in means

    def test_stop_syncs_device_work(self):
        timers = SynchronizedWallClockTimer()
        x = jnp.ones((256, 256))
        t = timers("matmul")
        t.start()
        y = x @ x
        t.stop(sync_obj=y)  # must not raise; blocks until ready
        assert t.elapsed() >= 0

    def test_noop(self):
        timers = NoopTimer()
        timers("x").start()
        timers("x").stop()
        assert timers.log(["x"]) == {}

    def test_trim_mean(self):
        assert trim_mean([1.0, 2.0, 3.0, 100.0], 0.25) == pytest.approx(2.5)
        assert trim_mean([], 0.1) == 0.0


class TestThroughputTimer:
    def test_samples_per_sec(self):
        tt = ThroughputTimer(batch_size=32, start_step=1, steps_per_output=100)
        for _ in range(4):
            tt.start()
            time.sleep(0.01)
            tt.stop(global_step=True)
        sps = tt.avg_samples_per_sec()
        # 3 counted steps of ~10ms each at batch 32 → ~3200 samples/s
        assert 500 < sps < 33000


class TestFlopsProfiler:
    def test_flops_of_matmul(self):
        n = 64
        a = jnp.ones((n, n), jnp.float32)
        f = flops_of(lambda x: x @ x, a)
        # 2*n^3 FLOPs, allow compiler slack
        assert f == pytest.approx(2 * n ** 3, rel=0.5)

    def test_get_model_profile(self):
        a = jnp.ones((32, 32), jnp.float32)
        flops, macs, params = get_model_profile(
            lambda x: x @ x + x, args=(a,), print_profile=False,
            as_string=False)
        assert flops > 0 and macs == pytest.approx(flops / 2)

    def test_get_model_profile_gpt2_block_known_geometry(self):
        """The attribution tree the roofline consumes, pinned against a
        hand-derived GPT-2 block formula: per-module jaxpr attribution
        must equal the analytic matmul FLOPs EXACTLY (both count
        2*M*N*K), and ``cost_analysis`` may only exceed it by the
        non-matmul tail (softmax/LN/gelu — a few percent)."""
        from deepspeed_tpu.models.gpt2 import GPT2Block, GPT2Config
        from deepspeed_tpu.profiling.flops_profiler.profiler import (
            module_tree, per_module_flops)

        B, S = 2, 64
        cfg = GPT2Config.tiny(hidden_size=128, num_attention_heads=4,
                              max_position_embeddings=128,
                              dtype=jnp.float32)
        H, I = cfg.hidden_size, cfg.mlp_dim
        blk = GPT2Block(cfg)
        x = jnp.ones((B, S, H), jnp.float32)
        params = blk.init(jax.random.key(0), x)["params"]

        def fn(p, x):
            return blk.apply({"params": p}, x)

        # hand formula: qkv (3H^2) + scores/values (2 * S*H per query
        # token) + out proj (H^2) + 2-layer MLP (2 * H*I), all 2*M*N*K
        analytic = (2 * B * S * 3 * H * H        # c_attn
                    + 2 * 2 * B * S * S * H      # q·k^T + att·v
                    + 2 * B * S * H * H          # attn_out
                    + 2 * 2 * B * S * H * I)     # c_fc + c_proj
        per_mod = per_module_flops(fn, params, x)
        assert sum(per_mod.values()) == pytest.approx(analytic, rel=1e-9)
        # the tree names the issuing modules (what the waterfall reads),
        # under the block's attn / mlp device scopes (PR 23)
        rolled = module_tree(per_mod, depth=3)
        for mod, want in (("GPT2Block/attn/c_attn", 2 * B * S * 3 * H * H),
                          ("GPT2Block/attn/attn_out", 2 * B * S * H * H),
                          ("GPT2Block/mlp/c_fc", 2 * B * S * H * I),
                          ("GPT2Block/mlp/c_proj", 2 * B * S * H * I)):
            assert rolled[mod] == pytest.approx(want, rel=1e-9), mod
        assert module_tree(per_mod, depth=2)["GPT2Block/mlp"] == \
            pytest.approx(2 * 2 * B * S * H * I, rel=1e-9)
        # compiler-exact total: matmuls dominate, tail is single-digit %
        flops, macs, _params = get_model_profile(
            fn, args=(params, x), print_profile=False, as_string=False)
        assert analytic <= flops <= 1.15 * analytic, (flops, analytic)
        assert macs == pytest.approx(flops / 2)

    def test_engine_profile_at_step(self, tmp_path):
        config = {
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "flops_profiler": {"enabled": True, "profile_step": 2,
                               "output_file": str(tmp_path / "prof.txt")},
        }
        model = SimpleModel(hidden_dim=16)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
        x, y = random_batch(8, 16)
        for _ in range(3):
            loss = engine(x, y)
            engine.backward(loss)
            engine.step()
        prof = engine.flops_profiler
        assert prof is not None
        assert prof.get_total_flops() > 0
        assert prof.get_total_params() > 0
        report = (tmp_path / "prof.txt").read_text()
        assert "Flops Profiler" in report

    def test_engine_wall_clock_breakdown(self):
        config = {
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "wall_clock_breakdown": True,
            "steps_per_print": 1,
        }
        model = SimpleModel(hidden_dim=16)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
        x, y = random_batch(8, 16)
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        from deepspeed_tpu.utils.timer import (FORWARD_MICRO_TIMER,
                                               STEP_MICRO_TIMER)

        names = engine.timers.get_timers()
        assert FORWARD_MICRO_TIMER in names and STEP_MICRO_TIMER in names
        assert engine.tput_timer.global_step_count == 1

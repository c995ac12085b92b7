"""Distributed-numerics tests on 8 forced host devices.

Each test runs in a subprocess (XLA device count is locked at first jax
init, so the main pytest process must keep seeing 1 device). The
subprocess asserts internally and exits non-zero on failure.
"""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, timeout: int = 600) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    prelude = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_smoke
        from repro.parallel.sharding import make_ctx
        from repro.models import lm
        mesh = jax.make_mesh((2, 4), ("data", "model"), devices=jax.devices(),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx = make_ctx(mesh)
        rng = np.random.default_rng(0)
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(body)],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=_ROOT,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}"


@pytest.mark.slow
def test_sharded_decode_matches_single_device():
    _run(
        """
        for arch in ["starcoder2-3b", "deepseek-v3-671b", "hymba-1.5b"]:
            cfg = dataclasses.replace(get_smoke(arch), dtype="float32", capacity_factor=64.0)
            params = lm.init_lm(jax.random.PRNGKey(0), cfg, tp=ctx.tp_size)
            B, S = 4, 17
            toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
            batch = {"tokens": toks}
            pre = {k: v[:, : S - 1] for k, v in batch.items()}
            last = {k: v[:, S - 1 :] for k, v in batch.items()}
            _, c0 = lm.prefill(params, pre, cfg, None, s_alloc=20, q_chunk=4, kv_chunk=4)
            ref, _ = lm.decode_step(params, c0, last, jnp.int32(S - 1), cfg, None)
            with jax.set_mesh(mesh):
                _, c1 = lm.prefill(params, pre, cfg, ctx, s_alloc=20, q_chunk=4, kv_chunk=4)
                dist, _ = lm.decode_step(params, c1, last, jnp.int32(S - 1), cfg, ctx)
            r, d = np.asarray(ref, np.float32), np.asarray(dist, np.float32)
            err = np.max(np.abs(r - d) / (np.abs(r) + 1e-2))
            assert err < 1e-3, (arch, err)
        print("ok")
        """
    )


@pytest.mark.slow
def test_serve_ep_matches_fsdp_placement():
    """Global-EP MoE serving path computes the same logits as baseline."""
    _run(
        """
        from repro.serving.steps import make_decode_step
        from repro.models import lm as lm_mod
        cfg = dataclasses.replace(get_smoke("qwen2-moe-a2.7b"), dtype="float32",
                                  capacity_factor=64.0)
        tp = ctx.tp_size
        params = lm_mod.init_lm(jax.random.PRNGKey(0), cfg, tp=tp)
        B, S_alloc = 4, 8
        cache = lm_mod.init_cache(cfg, B, S_alloc, tp)
        tok = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)}
        # pass uncommitted host trees so each jit is free to place them
        params_h = jax.tree.map(np.asarray, params)
        cache_h = jax.tree.map(np.asarray, cache)
        outs = {}
        with jax.set_mesh(mesh):
            for mode in ("fsdp", "tp"):
                step = make_decode_step(cfg, ctx, serve_sharding=mode)
                logits, _ = step(jax.tree.map(np.copy, params_h),
                                 jax.tree.map(np.copy, cache_h), tok, jnp.int32(0))
                outs[mode] = np.asarray(logits, np.float32)
        err = np.max(np.abs(outs["fsdp"] - outs["tp"]) / (np.abs(outs["fsdp"]) + 1e-2))
        assert err < 1e-3, err
        print("ok")
        """
    )


@pytest.mark.slow
def test_fsdp_all_train_step_matches_fsdp():
    """param_mode=fsdp_all computes the same loss/update as ZeRO-3+TP."""
    _run(
        """
        from repro.training.steps import TrainSettings, make_train_step
        from repro.training.optimizer import OptConfig, init_opt
        cfg = dataclasses.replace(get_smoke("yi-34b"), dtype="float32",
                                  d_model=64, n_heads=8, n_kv_heads=2)
        B, S = 8, 16
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 1)), jnp.int32)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        losses = {}
        params0 = lm.init_lm(jax.random.PRNGKey(0), cfg, tp=ctx.tp_size)
        opt0 = init_opt(params0, OptConfig(lr=1e-3, warmup_steps=1))
        params_h = jax.tree.map(np.asarray, params0)
        opt_h = jax.tree.map(np.asarray, opt0)
        with jax.set_mesh(mesh):
            for mode in ("fsdp", "fsdp_all"):
                settings = TrainSettings(remat="none", q_chunk=8, kv_chunk=8,
                                         param_mode=mode,
                                         opt=OptConfig(lr=1e-3, warmup_steps=1))
                step, _, _ = make_train_step(cfg, ctx, settings)
                _, _, metrics = step(jax.tree.map(np.copy, params_h),
                                     jax.tree.map(np.copy, opt_h), batch)
                losses[mode] = float(metrics["loss"])
        assert abs(losses["fsdp"] - losses["fsdp_all"]) < 1e-4, losses
        print("ok")
        """
    )


@pytest.mark.slow
def test_pipeline_over_pod_matches_baseline():
    """GPipe over the pod axis: identical loss through fwd+bwd+optimizer."""
    _run(
        """
        from repro.training.steps import TrainSettings, make_train_step
        from repro.training.optimizer import OptConfig, init_opt
        mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                              devices=jax.devices(),
                              axis_types=(jax.sharding.AxisType.Auto,) * 3)
        ctx3 = make_ctx(mesh3)
        cfg = dataclasses.replace(get_smoke("yi-34b"), dtype="float32")
        B, S = 8, 16
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 1)), jnp.int32)
        batch = {"tokens": np.asarray(toks[:, :-1]), "labels": np.asarray(toks[:, 1:])}
        params0 = lm.init_lm(jax.random.PRNGKey(0), cfg, tp=ctx3.tp_size)
        opt0 = init_opt(params0, OptConfig(lr=1e-3, warmup_steps=1))
        params_h = jax.tree.map(np.asarray, params0)
        opt_h = jax.tree.map(np.asarray, opt0)
        losses = {}
        with jax.set_mesh(mesh3):
            for pp in (0, 4):
                settings = TrainSettings(remat="none", q_chunk=8, kv_chunk=8,
                                         pipeline_micro=pp,
                                         opt=OptConfig(lr=1e-3, warmup_steps=1))
                step, _, _ = make_train_step(cfg, ctx3, settings)
                _, _, m = step(jax.tree.map(np.copy, params_h),
                               jax.tree.map(np.copy, opt_h), dict(batch))
                losses[pp] = float(m["loss"])
        assert abs(losses[0] - losses[4]) < 1e-4, losses
        print("ok")
        """
    )

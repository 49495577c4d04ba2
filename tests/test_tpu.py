"""Real-TPU opt-in suite: ``TL_TPU_TESTS=1 python -m pytest tests/test_tpu.py``.

The analog of the reference's env-gated true-cluster tests
(``tests/test_ddp_gpu.py:126-137``, opt-in via ``CLUSTER=1``): everything
else in ``tests/`` runs on the virtual CPU mesh; this module drives the one
real chip. The shared conftest pins this *process* to the CPU platform
before jax imports — so the pytest process never holds the chip — and each
test here runs in a subprocess with the original (pre-conftest)
``JAX_PLATFORMS``/``XLA_FLAGS`` restored: one process per chip, one at a
time, a fresh XLA client per test.

The main path on the chip — ``Trainer.fit`` at GPT-2-small width, serving
those params, every Pallas kernel against its XLA path (the flash kernel
included) — is ``chip_smoke.py``'s; the first test here runs it instead of
duplicating its flows. The rest pin what the smoke does not drive.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from tests.conftest import ORIGINAL_TPU_ENV

pytestmark = pytest.mark.tpu

needs_tpu = pytest.mark.skipif(
    os.environ.get("TL_TPU_TESTS") != "1",
    reason="real-TPU suite is opt-in: set TL_TPU_TESTS=1")


def _tpu_env() -> dict:
    env = dict(os.environ)
    for key, value in ORIGINAL_TPU_ENV.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    env.pop("TL_COORDINATOR_ADDRESS", None)
    env.pop("TL_NUM_PROCESSES", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run_on_tpu(body: str = "", timeout: int = 420, argv=None) -> dict:
    """Run a script (or ``argv``) on the real chip; it must print one
    JSON line last."""
    cmd = argv or [sys.executable, "-c", textwrap.dedent(body)]
    proc = subprocess.run(cmd, env=_tpu_env(), capture_output=True,
                          text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"TPU child failed (rc={proc.returncode}):\n--- stdout ---\n"
            f"{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


@needs_tpu
def test_chip_smoke_passes():
    """Fit on the chip, serve the result, and every Pallas kernel against
    its XLA path (flash attention included): ``chip_smoke.py`` drives all
    of it in one process and checks it by its own means."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = _run_on_tpu(timeout=1200, argv=[
        sys.executable, os.path.join(repo, "chip_smoke.py")])
    assert out["ok"] is True
    assert out["device"]["platform"] == "tpu"


@needs_tpu
def test_oversubscription_fails_loudly():
    """Asking for more chips than the host owns must raise, not wedge."""
    out = _run_on_tpu("""
        import json
        import jax
        from ray_lightning_tpu import RayStrategy, Trainer
        from ray_lightning_tpu.models import BoringModel

        n = len(jax.devices())
        trainer = Trainer(
            strategy=RayStrategy(num_workers=n + 3, use_tpu=True),
            max_epochs=1)
        try:
            trainer.fit(BoringModel())
            print(json.dumps({"raised": False}))
        except ValueError as e:
            print(json.dumps({"raised": True, "message": str(e)}))
    """)
    assert out["raised"] is True
    assert "devices" in out["message"]


@needs_tpu
def test_flash_attention_beyond_xla_limit():
    """T=16384 fwd+bwd through the pallas kernels on the real chip — a
    length where the XLA-dot path cannot even compile (its f32 score
    tensor is 12.9 GiB; round-5 probe: the compile helper dies). Past
    ~12k tokens flash is the only way to run, so this pins capability,
    not speed (docs/performance.md)."""
    out = _run_on_tpu("""
        import json
        import jax
        import jax.numpy as jnp
        from ray_lightning_tpu.ops.pallas_flash import (
            pallas_flash_attention)

        B, T, H, D = 1, 16384, 12, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, do = (jax.random.normal(x, (B, T, H, D),
                                         dtype=jnp.bfloat16) for x in ks)
        g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            pallas_flash_attention(q, k, v, causal=True)
            .astype(jnp.float32) * do.astype(jnp.float32)),
            argnums=(0, 1, 2)))
        dq, dk, dv = g(q, k, v)
        val = float(jax.device_get(dq.ravel()[0]))
        finite = bool(jax.device_get(
            jnp.isfinite(dq).all() & jnp.isfinite(dk).all()
            & jnp.isfinite(dv).all()))
        print(json.dumps({"platform": jax.devices()[0].platform,
                          "finite": finite, "sample": val}))
    """)
    assert out["platform"] == "tpu"
    assert out["finite"] is True


@needs_tpu
def test_lm_head_losses_on_chip():
    """The fused and chunked LM-head losses (the flagship bench's loss
    path) agree with the direct optax computation on real hardware —
    bf16 MXU matmuls with f32 reductions, not just the CPU interpreter."""
    out = _run_on_tpu("""
        import json
        import jax, jax.numpy as jnp, numpy as np, optax
        from ray_lightning_tpu.ops.lm_head_loss import (
            chunked_lm_head_xent, lm_head_xent)

        rng = np.random.default_rng(0)
        B, T, D, V = 4, 128, 64, 1024
        hidden = jnp.asarray(
            rng.standard_normal((B, T, D)) * 0.3, jnp.bfloat16)
        emb = jnp.asarray(rng.standard_normal((V, D)) * 0.05, jnp.float32)
        y = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)

        h32 = hidden.astype(jnp.float32)
        direct = optax.softmax_cross_entropy_with_integer_labels(
            (h32.reshape(-1, D) @ emb.T), y.reshape(-1)).mean()
        fused = jax.jit(lm_head_xent)(hidden, emb, y)
        chunked = jax.jit(
            lambda h, e, t: chunked_lm_head_xent(h, e, t, chunk_size=96)
        )(hidden, emb, y)
        print(json.dumps({
            "platform": jax.devices()[0].platform,
            "direct": float(direct), "fused": float(fused),
            "chunked": float(chunked)}))
    """)
    assert out["platform"] == "tpu"
    # bf16 logits vs f32 reference: loose but meaningful tolerance
    assert abs(out["fused"] - out["direct"]) / out["direct"] < 0.02
    assert abs(out["chunked"] - out["direct"]) / out["direct"] < 0.02


@needs_tpu
def test_memory_efficient_optimizer_and_save_attn_on_chip(tmp_path):
    """The round-4 GPT-2-medium levers on real hardware: a GPT fit with
    optimizer='adafactor' + the save_attn remat policy trains (loss
    falls) on the chip — the exact code path behind the bench's
    gpt2_medium config, at nano scale."""
    out = _run_on_tpu(f"""
        import json
        import jax
        from ray_lightning_tpu import RayStrategy, Trainer
        from ray_lightning_tpu.models import GPTModule
        from ray_lightning_tpu.models.gpt import gpt2_config

        cfg = gpt2_config(
            "nano", vocab_size=256, max_seq_len=64, remat=True,
            remat_policy="dots_with_no_batch_dims_save_attn")
        model = GPTModule(config=cfg, batch_size=8, seq_len=64,
                          num_samples=128, lr=1e-2,
                          optimizer="adafactor")
        trainer = Trainer(
            strategy=RayStrategy(num_workers=1, use_tpu=True),
            max_epochs=2, seed=0, limit_val_batches=2,
            num_sanity_val_steps=0, enable_checkpointing=False,
            default_root_dir={str(tmp_path)!r})
        trainer.fit(model)
        print(json.dumps({{
            "platform": jax.devices()[0].platform,
            "val_ppl": float(trainer.callback_metrics["val_ppl"]),
        }}))
    """)
    assert out["platform"] == "tpu"
    assert out["val_ppl"] < 100, f"did not learn: ppl={out['val_ppl']}"

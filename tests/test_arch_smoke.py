"""Per-architecture smoke tests (deliverable f): every assigned arch
instantiates its REDUCED config, runs one forward/train step and one
prefill→decode step on CPU, asserting output shapes and finiteness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.models import transformer as tfm
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import TrainConfig, make_train_step

# heavy lane: excluded from the fast CI default (`-m "not slow"`)
pytestmark = pytest.mark.slow


KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16):
    d = {"tokens": (jnp.arange(B * S).reshape(B, S) % cfg.vocab
                    ).astype(jnp.int32),
         "labels": (jnp.arange(B * S).reshape(B, S) % cfg.vocab
                    ).astype(jnp.int32)}
    if cfg.frontend == "vision":
        d["patches"] = jnp.ones((B, max(1, cfg.prefix_tokens), cfg.d_model),
                                jnp.float32)
    if cfg.enc_dec:
        d["frames"] = jnp.ones((B, cfg.enc_len, cfg.d_model), jnp.float32)
    return d


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_smoke(arch)
    params = tfm.init_params(KEY, cfg, jnp.float32)
    tc = TrainConfig(adamw=AdamWConfig(lr=1e-3, warmup_steps=1))
    step = make_train_step(cfg, tc, None)
    from repro.training.optimizer import init_opt_state
    opt = init_opt_state(params)
    batch = _batch(cfg)
    l0 = np.asarray(jax.tree.leaves(params)[0]).copy()   # donated below
    p2, o2, _, metrics = step(params, opt, jnp.zeros(()), batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    assert int(o2["step"]) == 1
    # a param actually moved
    l1 = np.asarray(jax.tree.leaves(p2)[0])
    assert not np.allclose(l0, l1)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode(arch):
    cfg = get_smoke(arch)
    params = tfm.init_params(KEY, cfg, jnp.float32)
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    st = tfm.init_decode_state(cfg, B, 64, dtype=jnp.float32)
    kw = {}
    if cfg.enc_dec:
        kw["enc_frames"] = batch["frames"]
    if cfg.frontend == "vision":
        kw["patches"] = batch["patches"]
    logits, st = tfm.prefill(params, cfg, batch["tokens"], st, **kw)
    assert logits.shape == (B, cfg.vocab)
    lg2, st2 = tfm.decode_step(params, cfg, st,
                               jnp.ones((B,), jnp.int32))
    assert lg2.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(lg2)).all()
    assert int(st2["lengths"][0]) == int(st["lengths"][0]) + 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_param_accounting(arch):
    """The FULL config's analytic parameter count matches init_params
    (checked structurally via eval_shape — no allocation)."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        lambda: tfm.init_params(KEY, cfg, jnp.bfloat16))
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == cfg.param_count(), (total, cfg.param_count())


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_depth_cut_config(arch):
    """``get_config(arch, n_layers=k)`` keeps the published widths and
    the first ``k`` layers' mixer/ffn patterns, and init_params agrees
    with its analytic parameter count."""
    full = get_config(arch)
    k = min(3, full.n_layers)
    cut = get_config(arch, n_layers=k)
    assert cut.n_layers == k
    assert cut.mixers == full.mixers[:k] and cut.ffns == full.ffns[:k]
    assert cut.with_(n_layers=full.n_layers, mixers=full.mixers,
                     ffns=full.ffns) == full
    shapes = jax.eval_shape(
        lambda: tfm.init_params(KEY, cut, jnp.bfloat16))
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert total == cut.param_count()
    for bad in (0, full.n_layers + 1):
        with pytest.raises(ValueError, match="layers"):
            get_config(arch, n_layers=bad)


def test_assigned_param_counts_sane():
    """Full configs land near their nameplate sizes."""
    expect = {"deepseek-7b": (6e9, 8e9), "deepseek-v2-236b": (220e9, 250e9),
              "deepseek-moe-16b": (15e9, 18e9), "qwen2.5-14b": (13e9, 16e9),
              "granite-3-8b": (7e9, 9.5e9), "rwkv6-7b": (6e9, 9.5e9),
              "jamba-v0.1-52b": (49e9, 55e9)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, (arch, n)

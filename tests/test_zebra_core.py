"""Unit + property tests for the Zebra core (the paper's mechanism)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (ZebraConfig, init_threshold_net, init_token_threshold_net,
                        zebra_cnn, zebra_tokens, zebra_infer_bitmap_nchw,
                        collect_zebra_loss, mean_zero_frac)

K = jax.random.PRNGKey(0)


def manual_block_mask(x, t, b):
    """Reference: per-(channel, b x b block) zero if max|block| < t."""
    B, C, H, W = x.shape
    y = np.array(x, np.float32)
    keep = np.zeros((B, C, H // b, W // b), bool)
    for bi in range(B):
        for c in range(C):
            for i in range(H // b):
                for j in range(W // b):
                    blk = y[bi, c, i*b:(i+1)*b, j*b:(j+1)*b]
                    k = np.max(np.abs(blk)) >= t
                    keep[bi, c, i, j] = k
                    if not k:
                        y[bi, c, i*b:(i+1)*b, j*b:(j+1)*b] = 0
    return y, keep


def test_infer_matches_manual():
    x = jax.nn.relu(jax.random.normal(K, (2, 3, 8, 8)))
    cfg = ZebraConfig(t_obj=0.8, block_hw=4, mode="infer")
    y, aux = zebra_cnn(x, cfg)
    y_ref, keep = manual_block_mask(np.asarray(x), 0.8, 4)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-6)
    assert np.isclose(float(aux["zero_frac"]), 1 - keep.mean(), atol=1e-6)


def test_bitmap_matches_mask():
    x = jax.random.normal(K, (2, 4, 8, 8))
    cfg = ZebraConfig(t_obj=1.2, block_hw=2, mode="infer")
    y, keep = zebra_infer_bitmap_nchw(x, cfg)
    y2, aux = zebra_cnn(x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2))


def test_train_mode_reg_pulls_to_tobj():
    """Eq. 1: the reg term is ||T_obj - T||^2 summed over channels."""
    x = jax.nn.relu(jax.random.normal(K, (4, 8, 8, 8)))
    tnet = init_threshold_net(K, 8)
    cfg = ZebraConfig(t_obj=0.5, block_hw=4, mode="train")
    _, aux = zebra_cnn(x, cfg, tnet)
    gap = jnp.mean(x, axis=(2, 3))
    thr = gap @ tnet["w"] + tnet["b"]
    expect = jnp.mean(jnp.sum((0.5 - thr) ** 2, axis=-1))
    assert np.isclose(float(aux["reg"]), float(expect), rtol=1e-5)


def test_gradient_modes():
    x = jax.random.normal(K, (2, 4, 8, 8))
    tnet = init_threshold_net(K, 4)
    for gm in ("hard", "ste", "soft"):
        cfg = ZebraConfig(t_obj=0.3, block_hw=4, mode="train", grad_mode=gm)

        def loss(xx):
            y, aux = zebra_cnn(xx, cfg, tnet)
            return jnp.sum(y ** 2)
        g = jax.grad(loss)(x)
        assert np.all(np.isfinite(np.asarray(g))), gm
    # hard: gradient is zero exactly on masked blocks (force thresholds
    # above every activation via the net's bias: T = GAP@W + b)
    tnet_hi = dict(tnet, b=tnet["b"] + 100.0)
    cfg = ZebraConfig(t_obj=10.0, block_hw=4, mode="train", grad_mode="hard")
    g = jax.grad(lambda xx: jnp.sum(zebra_cnn(xx, cfg, tnet_hi)[0] ** 2))(x)
    assert float(jnp.max(jnp.abs(g))) == 0.0
    # ste: gradient flows through masked blocks
    cfg = cfg.replace(grad_mode="ste")
    g = jax.grad(lambda xx: jnp.sum(zebra_cnn(xx, cfg, tnet)[0] * 1.0))(x)
    assert float(jnp.min(jnp.abs(g))) >= 0.0  # finite, defined everywhere


def test_threshold_only_reg_gradient_in_hard_mode():
    """Paper semantics: with hard masking, threshold-net weights learn only
    from the regularizer."""
    x = jax.nn.relu(jax.random.normal(K, (2, 4, 8, 8)))
    tnet = init_threshold_net(jax.random.PRNGKey(1), 4)
    cfg = ZebraConfig(t_obj=0.4, block_hw=4, mode="train", grad_mode="hard")

    def ce_only(tn):   # task-loss part only
        y, aux = zebra_cnn(x, cfg, tn)
        return jnp.sum(y ** 2)
    g = jax.grad(ce_only)(tnet)
    assert float(jnp.max(jnp.abs(g["w"]))) == 0.0

    def reg_only(tn):
        return zebra_cnn(x, cfg, tn)[1]["reg"]
    g2 = jax.grad(reg_only)(tnet)
    assert float(jnp.max(jnp.abs(g2["w"]))) > 0.0


@settings(max_examples=20, deadline=None)
@given(t=st.floats(0.0, 2.0), b=st.sampled_from([2, 4]),
       seed=st.integers(0, 2**30))
def test_property_block_all_or_none(t, b, seed):
    """Every b x b block is either untouched or exactly zero."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 2, 8, 8))
    cfg = ZebraConfig(t_obj=t, block_hw=b, mode="infer")
    y, _ = zebra_cnn(x, cfg)
    xn, yn = np.asarray(x), np.asarray(y)
    for c in range(2):
        for i in range(8 // b):
            for j in range(8 // b):
                blk_x = xn[0, c, i*b:(i+1)*b, j*b:(j+1)*b]
                blk_y = yn[0, c, i*b:(i+1)*b, j*b:(j+1)*b]
                assert (np.array_equal(blk_y, blk_x)
                        or not blk_y.any())


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**30))
def test_property_zero_frac_monotone_in_tobj(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 4, 8, 8))
    fracs = []
    for t in (0.0, 0.5, 1.0, 2.0, 4.0):
        cfg = ZebraConfig(t_obj=t, block_hw=4, mode="infer")
        _, aux = zebra_cnn(x, cfg)
        fracs.append(float(aux["zero_frac"]))
    assert all(a <= b + 1e-9 for a, b in zip(fracs, fracs[1:]))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**30))
def test_property_idempotent(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 4, 8, 8))
    cfg = ZebraConfig(t_obj=0.7, block_hw=4, mode="infer")
    y1, _ = zebra_cnn(x, cfg)
    y2, _ = zebra_cnn(y1, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))


def test_tokens_layout():
    x = jax.random.normal(K, (2, 32, 256))
    cfg = ZebraConfig(t_obj=0.5, block_seq=8, block_ch=128, mode="infer")
    y, aux = zebra_tokens(x, cfg)
    assert y.shape == x.shape
    assert aux["n_blocks"] == (32 // 8) * (256 // 128)
    # train mode with per-channel-block threshold net
    tnet = init_token_threshold_net(K, 256, 2)
    cfgt = cfg.replace(mode="train")
    y2, aux2 = zebra_tokens(x, cfgt, tnet)
    assert np.isfinite(float(aux2["reg"]))


def test_collect_and_mean():
    auxes = [
        {"reg": jnp.float32(1.0), "zero_frac": jnp.float32(0.5), "n_blocks": 10},
        {"reg": jnp.float32(2.0), "zero_frac": jnp.float32(0.0), "n_blocks": 30},
    ]
    assert float(collect_zebra_loss(auxes)) == 3.0
    assert np.isclose(float(mean_zero_frac(auxes)), 0.125)


def test_disabled_passthrough():
    x = jax.random.normal(K, (2, 4, 8, 8))
    y, aux = zebra_cnn(x, ZebraConfig(enabled=False))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# The NCHW block gate against its reshape/repeat form
# ---------------------------------------------------------------------------

def _oracle_block_max(x, b):
    """The block max as a 6-D reshape and ``jnp.max`` over its windows."""
    B, C, H, W = x.shape
    xb = x.reshape(B, C, H // b, b, W // b, b)
    return jnp.max(jnp.abs(xb), axis=(3, 5))


def _oracle_expand(mask_blocks, b):
    """The block map expanded by two chained ``jnp.repeat``s."""
    return jnp.repeat(jnp.repeat(mask_blocks, b, axis=2), b, axis=3)


def _gate_map(dtype, b, key=K):
    """(2, 3, 8, 8) map holding all-zero blocks, ties at the block max and
    negative values: values on a 0.5 grid, so maxima tie; a quarter of the
    blocks zeroed."""
    x = jnp.round(2.0 * jax.random.normal(key, (2, 3, 8, 8))) / 2.0
    dead = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.25,
                                (2, 3, 8 // b, 8 // b))
    return (x * _oracle_expand(~dead, b)).astype(dtype)


def _bits(a):
    """The array's bit patterns, so that -0.0 and 0.0 differ."""
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _assert_bitwise(a, b):
    assert a.shape == b.shape and a.dtype == b.dtype
    np.testing.assert_array_equal(_bits(a), _bits(b))


def _reshape_repeat(monkeypatch):
    """Point ``core.zebra``'s NCHW block helpers at the oracle forms."""
    from repro.core import zebra
    for name in ("_block_reduce_max_nchw", "_block_reduce_max_nchw_grad"):
        monkeypatch.setattr(zebra, name, _oracle_block_max)
    for name in ("_expand_mask_nchw", "_expand_mask_nchw_grad"):
        monkeypatch.setattr(zebra, name, _oracle_expand)


@pytest.mark.parametrize("b", [4, 2])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_block_gate_helpers_match_reshape_repeat(dtype, b):
    """The windowed block max and the one-broadcast expansion give the
    reshape/repeat results bit for bit, ties and dead blocks included."""
    from repro.core.zebra import _block_reduce_max_nchw, _expand_mask_nchw
    x = _gate_map(dtype, b)
    want = _oracle_block_max(x, b)
    assert bool(jnp.any(want == 0)) and bool(jnp.any(x < 0))
    _assert_bitwise(_block_reduce_max_nchw(x, b), want)
    for blocks in (want >= 1.0, want):
        _assert_bitwise(_expand_mask_nchw(blocks, b),
                        _oracle_expand(blocks, b))


@pytest.mark.parametrize("b", [4, 2])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_masked_maps_match_reshape_repeat(monkeypatch, dtype, b):
    """``zebra_cnn`` (infer, and constant-threshold and threshold-net
    training in every gradient mode) and ``zebra_infer_bitmap_nchw`` mask
    a map bit for bit as the reshape/repeat gate does, and keep the same
    blocks."""
    x = _gate_map(dtype, b)
    tnet = init_threshold_net(jax.random.PRNGKey(3), 3)
    tnet = dict(tnet, b=tnet["b"] + 1.0)
    cfg = ZebraConfig(t_obj=1.0, block_hw=b, mode="infer")
    runs = [(cfg, None)] + [
        (cfg.replace(mode="train", use_tnet=u, grad_mode=g), tnet)
        for u in (False, True) for g in ("hard", "ste", "soft")]

    def outputs():
        out = list(zebra_infer_bitmap_nchw(x, cfg))
        for c, tn in runs:
            y, aux = zebra_cnn(x, c, tn)
            out += [y, aux["zero_frac"]]
        return out

    got = outputs()
    keep = got[1]
    assert 0 < int(jnp.sum(keep)) < keep.size
    _reshape_repeat(monkeypatch)
    for g, w in zip(got, outputs(), strict=True):
        _assert_bitwise(g, w)


@pytest.mark.parametrize("grad_mode", ["hard", "ste", "soft"])
def test_tnet_gradients_match_reshape_repeat(monkeypatch, grad_mode):
    """Threshold-net training: the gradients to the map and to the net
    equal those of the reshape/repeat gate (the soft path differentiates
    through the block max, where ``jnp.max`` splits a tied block's
    gradient, and through the expansion)."""
    x = _gate_map(jnp.float32, 4)
    tnet = init_threshold_net(jax.random.PRNGKey(3), 3)
    tnet = dict(tnet, b=tnet["b"] + 1.0)
    cfg = ZebraConfig(t_obj=1.0, block_hw=4, mode="train",
                      grad_mode=grad_mode, soft_temp=0.5)

    def grads():
        def loss(xx, tn):
            y, aux = zebra_cnn(xx, cfg, tn)
            return jnp.sum(jnp.sin(y)) + aux["reg"]
        return jax.grad(loss, argnums=(0, 1))(x, tnet)

    got = grads()
    _reshape_repeat(monkeypatch)
    want = grads()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        _assert_bitwise(g, w)
    assert float(jnp.max(jnp.abs(got[0]))) > 0.0

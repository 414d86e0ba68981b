"""Dense flash Pallas kernel: forward + gradient parity vs the dense
einsum oracle, run in interpreter mode on CPU (the same single-code-path
strategy as the block-sparse kernel tests)."""

import dataclasses

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
import pytest
from jaxpr_tools import kernel_call_sites

from alphafold2_tpu.ops import attention
from alphafold2_tpu.ops.attention import (AttentionConfig, attention_apply,
                                          attention_init)
from alphafold2_tpu.ops.flash import flash_attention, rows_saved_bytes
from alphafold2_tpu.ops.flash_kernel import flash_attention_tpu, supported


def _dense(q, k, v, bias, scale):
    logits = jnp.einsum("bihd,bjhd->bhij", q, k).astype(jnp.float32) * scale
    logits = logits + bias[:, None, None, :]
    attn = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows: dense softmax of all -inf is nan — zero them like
    # the kernel does
    attn = jnp.where(jnp.isnan(attn), 0.0, attn)
    return jnp.einsum("bhij,bjhd->bihd", attn.astype(q.dtype), v)


def test_supported_shapes():
    assert supported(1024, 2048, 64)
    # streaming design: K/V and Q/G blocks are never fully resident, so
    # long axes previously rejected (whole-K/V-per-row residency) now run
    # in the kernel instead of falling back to XLA streaming
    assert supported(16, 10 ** 6, 64)
    assert supported(262144, 16384, 64)
    # only the f32 row vectors (bias 4j; lse+delta 8i) bound the length
    assert not supported(16, 10 ** 7, 64)
    assert not supported(10 ** 7, 16, 64)
    assert not supported(16, 16, 7)


def test_use_kernel_true_raises_on_unsupported():
    q = jnp.zeros((1, 8, 1, 7))  # dh=7 unsupported
    k = v = jnp.zeros((1, 8, 1, 7))
    with pytest.raises(ValueError, match="does not support"):
        flash_attention(q, k, v, use_kernel=True)


def _check_matches_dense(B, i, j, qb, kb, dtype, seed=0, label=""):
    """Kernel-vs-dense-oracle parity at one shape (shared by the
    parametrized cases and the fuzzed sweep)."""
    h, dh = 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, i, h, dh), dtype)
    k = jax.random.normal(ks[1], (B, j, h, dh), dtype)
    v = jax.random.normal(ks[2], (B, j, h, dh), dtype)
    mask = jax.random.bernoulli(ks[3], 0.8, (B, j)).at[:, 0].set(True)
    bias = jnp.where(mask, 0.0, float("-inf")).astype(jnp.float32)

    def fold(t):
        return t.transpose(0, 2, 1, 3).reshape(B * h, t.shape[1], dh)

    out = flash_attention_tpu(
        fold(q), fold(k), fold(v), jnp.repeat(bias, h, axis=0),
        dh ** -0.5, qb, kb,
    )
    assert out.dtype == dtype
    got = out.reshape(B, h, i, dh).transpose(0, 2, 1, 3)
    # the f32 oracle bounds the bf16 path's rounding, not its math
    want = _dense(q.astype(jnp.float32), k.astype(jnp.float32),
                  v.astype(jnp.float32), bias, dh ** -0.5)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=atol,
        err_msg=label,
    )


@pytest.mark.parametrize(
    "B,i,j,qb,kb,dtype",
    [
        (2, 64, 64, 16, 16, jnp.float32),   # square, multiple blocks
        (1, 40, 72, 16, 32, jnp.float32),   # cross shapes + padding both axes
        (2, 16, 16, 16, 16, jnp.float32),   # single tile
        # bf16 operands: the kernel's p/ds casts and f32-accumulation path
        # are identity under f32, so this is the ONLY default-tier coverage
        # of the bf16 dot layout the TPU workload runs
        (2, 64, 64, 16, 16, jnp.bfloat16),
    ],
)
def test_kernel_matches_dense(B, i, j, qb, kb, dtype):
    _check_matches_dense(B, i, j, qb, kb, dtype)


@pytest.mark.parametrize(
    "dtype",
    [jnp.float32, pytest.param(jnp.bfloat16, marks=pytest.mark.slow)],
)
def test_kernel_gradients_match_dense(dtype):
    # bf16 exercises the backward's ds/p operand-dtype casts in the
    # dq/dkv kernels (identity under f32); the f32 oracle bounds rounding
    B, i, j, h, dh = 1, 48, 40, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, i, h, dh), dtype)
    k = jax.random.normal(ks[1], (B, j, h, dh), dtype)
    v = jax.random.normal(ks[2], (B, j, h, dh), dtype)
    mask = jax.random.bernoulli(ks[3], 0.75, (B, j)).at[:, 0].set(True)
    bias = jnp.where(mask, 0.0, float("-inf")).astype(jnp.float32)

    def loss_kernel(q, k, v):
        o = flash_attention(
            q, k, v, bias, scale=dh ** -0.5, use_kernel=True
        )
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    def loss_dense(q, k, v):
        o = _dense(q.astype(jnp.float32), k.astype(jnp.float32),
                   v.astype(jnp.float32), bias, dh ** -0.5)
        return jnp.sum(jnp.sin(o))

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    for a, b in zip(g1, g2):
        assert a.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=atol
        )


def test_kernel_fully_masked_rows():
    B, i, j, h, dh = 1, 16, 16, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, i, h, dh))
    k = jax.random.normal(ks[1], (B, j, h, dh))
    v = jax.random.normal(ks[2], (B, j, h, dh))
    bias = jnp.full((B, j), float("-inf"), jnp.float32)

    out = flash_attention(q, k, v, bias, scale=dh ** -0.5, use_kernel=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), 0.0)

    g = jax.grad(
        lambda q: jnp.sum(
            flash_attention(q, k, v, bias, scale=dh ** -0.5, use_kernel=True)
        )
    )(q)
    assert np.isfinite(np.asarray(g)).all()


def test_pick_block_minimizes_padding():
    from alphafold2_tpu.ops.flash_kernel import pick_block

    # n=1152: 384 pads to exactly 1152; a fixed 512 would pad to 1536
    assert pick_block(1152) == 384
    assert pick_block(512) == 512
    assert pick_block(100) == 128   # below one block: round up to mult
    assert pick_block(1280) == 256  # 1280 = 5*256, zero padding
    # small padding savings don't justify tiny blocks: 896 keeps 512
    # (+14% padding) over 128 (0% padding, 7x the grid steps)
    assert pick_block(896) == 512
    for n in (8, 96, 640, 1000, 4096):
        b = pick_block(n)
        assert b % 128 == 0 and b <= 512
        padded = -(-n // b) * b
        # never worse than the fixed-512 legacy choice
        assert padded <= -(-n // 512) * 512


def test_block_target_shrinks_with_head_dim():
    from alphafold2_tpu.ops.flash_kernel import _block_target

    assert _block_target(64) == 512    # framework head dim: full blocks
    assert _block_target(512) == 256   # near the VMEM residency cap
    for dh in (8, 64, 128, 256, 512):
        t = _block_target(dh)
        assert 128 <= t <= 512 and t % 128 == 0


@pytest.mark.slow
def test_kernel_matches_dense_fuzzed_shapes():
    """Randomized (i, j, block) shapes sweep the padding edge cases —
    lengths below/above/straddling one block, blocks dividing the padded
    length unevenly — plus pinned degenerate trials at i=1 and j=1."""
    rs = np.random.RandomState(0)
    trials = [  # pinned degenerate rows first
        (1, 1, 33, 16, 16),
        (1, 33, 1, 16, 16),
        (2, 1, 1, 8, 8),
    ]
    for _ in range(10):
        trials.append((
            int(rs.randint(1, 3)),
            int(rs.randint(1, 70)),
            int(rs.randint(1, 70)),
            int(rs.choice([8, 16, 32])),
            int(rs.choice([8, 16, 32])),
        ))
    for t, (B, i, j, qb, kb) in enumerate(trials):
        _check_matches_dense(
            B, i, j, qb, kb, jnp.float32, seed=t,
            label=f"trial {t}: B={B} i={i} j={j} qb={qb} kb={kb}",
        )


# ---------------------------------------------------------------------------
# whole-row form: one grid step holds every key of a (batch, head group)
# ---------------------------------------------------------------------------


def _rows_case(B, i, j, h, dh, dtype, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, i, h, dh), dtype)
    k = jax.random.normal(ks[1], (B, j, h, dh), dtype)
    v = jax.random.normal(ks[2], (B, j, h, dh), dtype)
    keep = jax.random.bernoulli(ks[3], 0.7, (B, j)).at[:, 0].set(True)
    keep = keep.at[0].set(False)  # batch row 0: every key masked
    bias = jnp.where(keep, 0.0, float("-inf")).astype(jnp.float32)
    return q, k, v, bias


def test_rows_plan_follows_the_shape():
    from alphafold2_tpu.ops.flash_kernel import rows_plan

    # the pair stream's axial shape: two heads fill 128 lanes, and the
    # whole 1152-row logit tile is one chunk
    assert rows_plan(1152, 1152, 8, 64) == (2, 1152)
    assert rows_plan(1152, 1152, 8, 128) == (1, 1152)
    assert rows_plan(1000, 1100, 4, 32) == (4, 1024)  # padded lengths
    # the chunk shrinks to a divisor of the padded i when the tile grows
    assert rows_plan(2048, 2048, 8, 64) == (2, 512)
    # long keys stream (the form measured for j >= 4096), as do heads
    # that do not group to 128 lanes
    assert rows_plan(1152, 4096, 8, 64) is None
    assert rows_plan(128, 4096, 8, 64) is None
    assert rows_plan(1152, 1152, 3, 64) is None
    assert rows_plan(1152, 1152, 8, 48) is None
    assert rows_plan(64, 64, 2, 8) is None


@pytest.mark.parametrize(
    "B,i,j,h,dh",
    [
        (2, 256, 256, 4, 64),   # multiples of 128, two head groups
        (2, 200, 300, 2, 64),   # neither length a multiple of 128
        (2, 128, 256, 4, 32),   # four heads a grid step
        (2, 130, 128, 2, 128),  # one head a grid step
    ],
)
def test_whole_row_matches_blockwise_forward_and_gradients(B, i, j, h, dh):
    """float32, masked keys and a fully masked batch row, against
    `blockwise_attention`: the output and the gradients of q, k, v."""
    from alphafold2_tpu.ops.flash import blockwise_attention
    from alphafold2_tpu.ops.flash_kernel import rows_plan

    assert rows_plan(i, j, h, dh, 4) is not None
    q, k, v, bias = _rows_case(B, i, j, h, dh, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(11), q.shape)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    kern = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, bias, use_kernel=True)
    ref = lambda q, k, v: blockwise_attention(q, k, v, bias)  # noqa: E731
    got, want = kern(q, k, v), ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    assert not np.asarray(got[0]).any()  # the fully masked row: zeros
    g1 = jax.grad(loss(kern), (0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6)
    # masked keys take exactly no gradient
    dead = np.asarray(bias) == float("-inf")
    assert not np.asarray(g1[1])[dead].any()
    assert not np.asarray(g1[2])[dead].any()


def test_whole_row_chunked_queries_match_one_chunk(monkeypatch):
    """The query-chunk loop (a tile budget under the whole tile) gives
    the single chunk's numbers."""
    from alphafold2_tpu.ops import flash_kernel

    q, k, v, bias = _rows_case(2, 384, 256, 2, 64, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(12), q.shape)

    def run():
        f = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, bias, use_kernel=True)
        return f(q, k, v), jax.grad(
            lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(q, k, v)

    assert flash_kernel.rows_plan(384, 256, 2, 64, 4) == (2, 384)
    one_out, one_g = run()
    monkeypatch.setattr(flash_kernel, "_ROWS_TILE_BYTES", 128 * 256 * 4)
    assert flash_kernel.rows_plan(384, 256, 2, 64, 4) == (2, 128)
    out, g = run()
    np.testing.assert_allclose(np.asarray(out), np.asarray(one_out), atol=1e-6)
    for a, b in zip(g, one_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def test_whole_row_bf16_operands():
    """bf16 operands (the TPU workload's dot layout: p and ds cast to
    bf16, f32 accumulation) against the f32 oracle."""
    B, i, j, h, dh = 2, 128, 256, 2, 64
    q, k, v, bias = _rows_case(B, i, j, h, dh, jnp.bfloat16)
    bias = bias.at[0, :8].set(0.0)
    got = flash_attention(q, k, v, bias, use_kernel=True)
    assert got.dtype == jnp.bfloat16
    want = _dense(q.astype(jnp.float32), k.astype(jnp.float32),
                  v.astype(jnp.float32), bias, dh ** -0.5)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=2e-2)
    g = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
        q, k, v, bias, use_kernel=True).astype(jnp.float32))), (0, 1, 2))(q, k, v)
    g32 = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(_dense(
        q, k, v, bias, dh ** -0.5))), (0, 1, 2))(
            *(t.astype(jnp.float32) for t in (q, k, v)))
    for a, b in zip(g, g32):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=5e-2, rtol=2e-2)


def test_streaming_form_unchanged_at_long_j():
    """j >= 4096 keeps the streaming (multi-key-block) form, bit for bit
    what the folded-heads kernel gives, and equal to the XLA arm."""
    from alphafold2_tpu.ops.flash import blockwise_attention
    from alphafold2_tpu.ops.flash_kernel import pick_block, rows_plan

    B, i, j, h, dh = 1, 128, 4096 + 40, 2, 64
    assert rows_plan(i, j, h, dh, 4) is None
    assert pick_block(j) == 512
    q, k, v, bias = _rows_case(B, i, j, h, dh, jnp.float32)
    bias = bias.at[0, ::3].set(0.0)

    def fold(t):
        return t.transpose(0, 2, 1, 3).reshape(B * h, t.shape[1], dh)

    got = flash_attention(q, k, v, bias, use_kernel=True)
    direct = flash_attention_tpu(
        fold(q), fold(k), fold(v), jnp.repeat(bias, h, axis=0), dh ** -0.5
    ).reshape(B, h, i, dh).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(direct))
    want = blockwise_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


# --- what a batch chunk's checkpoint keeps -----------------------------------

_ARM = "AF2_KERNEL_BACKEND_FLASH_ATTENTION"
# four folded rows in chunks of two; 200 positions pad to the kernel's 256
_CHUNKED = AttentionConfig(dim=32, heads=2, dim_head=64, flash=True, batch_chunk=2)
_ROWS, _N = 4, 200


def _chunked_case():
    params = attention_init(jax.random.PRNGKey(0), _CHUNKED)
    x = jax.random.normal(jax.random.PRNGKey(1), (_ROWS, _N, _CHUNKED.dim))
    mask = jnp.ones((_ROWS, _N), bool).at[:, -5:].set(False)
    return params, x, mask


def _bare_checkpoint(monkeypatch):
    """The chunk under a `jax.checkpoint` without a policy, which builds
    the whole chunk again, the core's forward kernel included."""
    monkeypatch.setattr(attention, "_checkpointed_chunk", jax.checkpoint)


def _kept_by_one_chunk(capsys, cfg, params, x, **chunk):
    """The lines `print_saved_residuals` gives for one chunk's whole op
    under the chunk's checkpoint, its arguments and constants left out."""
    inner = dataclasses.replace(cfg, batch_chunk=0)
    jax.ad_checkpoint.print_saved_residuals(
        attention._checkpointed_chunk(
            lambda p, x, **rest: attention_apply(p, inner, x, **rest)),
        params, x, **chunk)
    return [line for line in capsys.readouterr().out.splitlines()
            if "from the argument" not in line and "from a constant" not in line]


def _nbytes(line):
    """Bytes of the array a `print_saved_residuals` line names: `f32[2,256,128] ...`."""
    dtype, dims = line.split("]")[0].split("[")
    return (int(np.prod([int(d) for d in dims.split(",")]))
            * jnp.dtype({"f32": "float32", "bf16": "bfloat16"}[dtype]).itemsize)


@pytest.mark.parametrize("checkpoint,forward_sites", [("saved_names", 1), ("bare", 2)])
def test_differentiated_chunks_call_the_forward_kernel_once(monkeypatch, checkpoint,
                                                            forward_sites):
    """The gradient of a batch-chunked `attention_apply` with the kernel arm
    (interpret mode, a shape the whole-row form takes): ONE call site of the
    forward kernel and one of the backward kernel, since the chunk's
    checkpoint keeps `out` and `lse`. The case this guards is the bare
    checkpoint's, whose backward map runs the forward kernel again."""
    monkeypatch.setenv(_ARM, "pallas_tpu")
    if checkpoint == "bare":
        _bare_checkpoint(monkeypatch)
    params, x, mask = _chunked_case()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(attention_apply(p, _CHUNKED, x, mask=mask) ** 2),
        (0, 1)))(params, x)
    assert kernel_call_sites(jaxpr.jaxpr) == {
        "_rows_fwd_kernel": forward_sites, "_rows_bwd_kernel": 1}


@pytest.mark.parametrize("arm", ["pallas_tpu", "xla_ref"])
def test_chunk_checkpoint_keeps_the_kernels_results_and_nothing_else(
        monkeypatch, capsys, arm):
    """With the kernel arm a chunk keeps, besides its arguments, the core's
    `out` (chunk, i padded, h * dh) and `lse`, which is what
    `rows_saved_bytes` says of it; with the XLA arm it holds no such name
    and keeps nothing. Either way the value and every gradient are the
    bare checkpoint's."""
    monkeypatch.setenv(_ARM, arm)
    params, x, mask = _chunked_case()
    chunk, h, dh = _CHUNKED.batch_chunk, _CHUNKED.heads, _CHUNKED.dim_head
    kept = _kept_by_one_chunk(capsys, _CHUNKED, params, x[:chunk], mask=mask[:chunk])
    saved = rows_saved_bytes(chunk, _N, _N, h, dh, jnp.float32)
    if arm == "xla_ref":
        assert kept == [] and saved == {}
    else:
        assert len(kept) == 2 and all("flash_kernel.py" in line for line in kept)
        assert kept[0].startswith(f"f32[{chunk},256,{h * dh}] ")
        assert "named 'attn_core_lse'" in kept[1]
        assert saved == {"attn_core_out": _nbytes(kept[0]),
                         "attn_core_lse": _nbytes(kept[1])}
        # the kernel's streaming form (long keys) carries no name
        assert rows_saved_bytes(chunk, _N, 4096, h, dh, jnp.float32) == {}

    def value_and_grad():
        return jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(attention_apply(p, _CHUNKED, x, mask=mask) ** 2),
            (0, 1)))(params, x)

    value, grads = value_and_grad()
    _bare_checkpoint(monkeypatch)
    want, want_grads = value_and_grad()
    assert abs(float(value) - float(want)) <= 1e-6 * abs(float(want))
    for got, ref in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.max(jnp.abs(got - ref))) <= 1e-6 * float(jnp.max(jnp.abs(ref)))


def test_cross_attention_chunk_keeps_nothing(monkeypatch, capsys):
    """A cross-attention chunk (`context` given, few keys: the
    materialized-logits arm, which never reaches the dispatcher) holds no
    kernel's name, whatever arm the dispatcher would take: it is built
    again whole, as before."""
    monkeypatch.setenv(_ARM, "pallas_tpu")
    cfg = dataclasses.replace(_CHUNKED, flash="auto")
    params, x, mask = _chunked_case()
    context = jax.random.normal(jax.random.PRNGKey(2), (_ROWS, 32, cfg.dim))
    chunk = cfg.batch_chunk
    assert _kept_by_one_chunk(capsys, cfg, params, x[:chunk], mask=mask[:chunk],
                              context=context[:chunk]) == []
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(attention_apply(
        p, cfg, x, mask=mask, context=context) ** 2)))(params)
    assert kernel_call_sites(jaxpr.jaxpr) == {}

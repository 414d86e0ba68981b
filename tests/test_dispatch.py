"""The kernel dispatch surface (ops/dispatch.py + ops/knobs.py, PR 13).

Three layers of pinning:

  * **Chip-free parity tier** — for every registered op, the kernel arm
    (Pallas interpret mode on this CPU host) must equal the `xla_ref`
    arm, over f32/bf16 and at least one PADDED shape (not a block
    multiple). These are the tests the af2lint `dispatch` pass requires
    every op to register — an op without one fails CI.
  * **Resolution semantics** — the ONE resolver's contract: the
    decision table of the attention core (`test_attention_core_choice`),
    caller forcing, the AF2_KERNEL_BACKEND global/per-op overrides (the
    one override channel), loud errors on unknown arms / unsupported
    shapes, and the introspection CLI output.
  * **The lint pass itself** — fires on fixture violations (missing
    xla_ref arm, unregistered parity test, kernel import outside ops/,
    AF2_* env read outside knobs.py) and stays silent on this repo.

Plus the cross-backend bench-matrix contract: sweep rows carrying
platform/backend_arm fields gate platform-qualified — a CPU row can
NEVER diff against a TPU row of the same leg.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.ops import dispatch, knobs
from alphafold2_tpu.ops.flash import (
    blockwise_attention,
    flash_attention,
    hop_attention_lse,
    merge_lse,
    stream_block,
    streamed_fused_attention,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ALL_BACKEND_ENVS = (
    ["AF2_KERNEL_BACKEND"]
    + [f"AF2_KERNEL_BACKEND_{op.upper()}" for op in dispatch.ops()]
)


@pytest.fixture(autouse=True)
def _clean_backend_env(monkeypatch):
    """No inherited override may leak into resolution asserts."""
    for name in _ALL_BACKEND_ENVS:
        monkeypatch.delenv(name, raising=False)
    yield


# ---------------------------------------------------------------------------
# chip-free parity tier: kernel arm (interpret) == xla_ref, f32/bf16 +
# one padded shape — registered with the dispatch lint per op
# ---------------------------------------------------------------------------


def _qkv(B, i, j, h, dh, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, i, h, dh), dtype)
    k = jax.random.normal(ks[1], (B, j, h, dh), dtype)
    v = jax.random.normal(ks[2], (B, j, h, dh), dtype)
    mask = jax.random.bernoulli(ks[3], 0.85, (B, j)).at[:, 0].set(True)
    bias = jnp.where(mask, 0.0, float("-inf")).astype(jnp.float32)
    return q, k, v, bias


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("i,j", [(32, 48), (24, 37)])  # 37: padded shape
def test_parity_flash_attention(monkeypatch, dtype, i, j):
    q, k, v, bias = _qkv(2, i, j, 2, 8, dtype)
    outs = {}
    for arm in ("pallas_tpu", "xla_ref"):
        monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", arm)
        assert dispatch.resolve("flash_attention", request="auto",
                                i=i, j=j, dh=8) == arm
        outs[arm] = np.asarray(
            flash_attention(q, k, v, bias, use_kernel="auto"), np.float32
        )
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(outs["pallas_tpu"], outs["xla_ref"],
                               atol=atol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("i,j", [(24, 24), (19, 29)])  # 19/29: padded
def test_parity_fused_attention(monkeypatch, dtype, i, j):
    B, h, dh = 2, 2, 8
    q, k, v, bias = _qkv(B, i, j, h, dh, dtype, seed=1)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    pair_bias = jax.random.normal(ks[0], (B, h, i, j), jnp.float32)
    gate = jax.random.normal(ks[1], (B, i, h, dh), dtype)
    outs = {}
    for arm in ("pallas_tpu", "xla_ref"):
        monkeypatch.setenv("AF2_KERNEL_BACKEND_FUSED_ATTENTION", arm)
        assert dispatch.resolve("fused_attention", request="auto",
                                i=i, j=j, dh=dh) == arm
        outs[arm] = np.asarray(
            flash_attention(q, k, v, bias, pair_bias=pair_bias, gate=gate,
                            use_kernel="auto"),
            np.float32,
        )
    atol = 5e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(outs["pallas_tpu"], outs["xla_ref"],
                               atol=atol)


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(16, 32, 24), (13, 40, 21)])  # padded
def test_parity_quant_matmul(monkeypatch, x_dtype, m, k, n):
    from alphafold2_tpu.ops.quant import quant_matmul, quantize_weight

    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(ks[0], (m, k), x_dtype)
    qw, scale = quantize_weight(jax.random.normal(ks[1], (k, n)))
    outs = {}
    for arm in ("pallas_tpu", "xla_ref"):
        monkeypatch.setenv("AF2_KERNEL_BACKEND_QUANT_MATMUL", arm)
        assert dispatch.resolve("quant_matmul", request="auto",
                                m=m, k=k, n=n, x_dtype=x.dtype) == arm
        outs[arm] = np.asarray(quant_matmul(x, qw, scale), np.float32)
    atol = 5e-4 if x_dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(outs["pallas_tpu"], outs["xla_ref"],
                               atol=atol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [64, 50])  # 50: pads to the 16-block grid
def test_parity_sparse_attention(monkeypatch, dtype, n):
    from alphafold2_tpu.ops.attention import AttentionConfig, attention_init
    from alphafold2_tpu.ops.sparse import SparseConfig, sparse_attention_apply

    cfg = AttentionConfig(dim=16, heads=2, dim_head=8, dtype=dtype)
    scfg = SparseConfig(block_size=16, num_local_blocks=2,
                        num_random_blocks=1, max_seq_len=128)
    params = attention_init(jax.random.PRNGKey(3), cfg)
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(1, n, 16), dtype)
    mask = jnp.asarray(rs.rand(1, n) > 0.1)
    outs = {}
    for arm in ("pallas_tpu", "xla_ref"):
        monkeypatch.setenv("AF2_KERNEL_BACKEND_SPARSE_ATTENTION", arm)
        assert dispatch.resolve("sparse_attention", request="auto",
                                n=n) == arm
        outs[arm] = np.asarray(
            sparse_attention_apply(params, cfg, scfg, x, mask=mask),
            np.float32,
        )
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(outs["pallas_tpu"], outs["xla_ref"],
                               atol=atol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal_n,h,dh,dv,sub", [
    (64, 2, 24, 16, 32),
    (40, 2, 24, 16, 32),     # padded to the blocks
    (96, 2, 24, 16, 32),     # three query blocks: below, on and above the diagonal
    (96, 4, 24, 16, 16),     # the query block larger than the sub-tile; four heads
    (80, 2, 192, 128, 32),   # the decoder's head sizes, padded
    (96, 4, 192, 128, 16),   # two head groups a batch row
])
def test_parity_flash_attention_causal(monkeypatch, dtype, causal_n, h, dh, dv,
                                       sub):
    """The causal call (v heads of their own size) resolves through the
    same op: kernel arm (interpret) == the XLA streaming arm."""
    from alphafold2_tpu.ops import flash_kernel

    n = causal_n
    q, k, _, _ = _qkv(2, n, n, h, dh, dtype, seed=3)
    v = jax.random.normal(jax.random.PRNGKey(4), (2, n, h, dv), dtype)
    outs = {}
    for arm in ("pallas_tpu", "xla_ref"):
        monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", arm)
        assert dispatch.resolve("flash_attention", request="auto", i=n, j=n,
                                dh=dh, dv=dv, causal=True) == arm
        outs[arm] = np.asarray(flash_attention(
            q, k, v, causal=True, kernel_qb=32, kernel_kb=sub, kv_block=32),
            np.float32)
    np.testing.assert_allclose(outs["pallas_tpu"], outs["xla_ref"],
                               atol=2e-5 if dtype == jnp.float32 else 2e-2)
    # cross lengths are not causal self-attention: the gate says so
    assert not flash_kernel.supported_causal(64, 128, 24, 16)
    with pytest.raises(ValueError, match="does not support"):
        dispatch.resolve("flash_attention", request=True, platform="tpu",
                         i=64, j=128, dh=24, dv=16, causal=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_parity_grouped_matmul(monkeypatch, dtype):
    """Kernel arm (megablox, interpret) == `jax.lax.ragged_dot` == a loop
    over the groups, forward and both gradients, with the rows past the
    groups' sum (unspecified) left out of the comparison."""
    from alphafold2_tpu.ops.moe import grouped_matmul

    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    x = jax.random.normal(ks[0], (256, 128), dtype)
    w = jax.random.normal(ks[1], (4, 128, 256), dtype) / 8
    counts = [70, 0, 130, 24]  # 32 rows in no group
    sizes, live = jnp.array(counts, jnp.int32), sum(counts)

    def loop(x, w):
        at, parts = 0, []
        for e, n in enumerate(counts):
            parts.append(x[at:at + n].astype(jnp.float32) @ w[e].astype(jnp.float32))
            at += n
        return jnp.concatenate(parts)

    def run(fn):
        out, grads = jax.value_and_grad(
            lambda x, w: jnp.sum(jnp.sin(fn(x, w)[:live].astype(jnp.float32))),
            argnums=(0, 1))(x, w)
        return [np.asarray(t, np.float32) for t in (out, grads[0][:live], grads[1])]

    want = run(loop)
    for arm in ("pallas_tpu", "xla_ref"):
        monkeypatch.setenv("AF2_KERNEL_BACKEND_GROUPED_MATMUL", arm)
        dispatch.reset_decisions()
        got = run(lambda x, w: grouped_matmul(x, w, sizes))
        assert dispatch.decisions() == {
            f"grouped_matmul -> {arm} @ m=256 k=128 n=256 groups=4": 1}
        for g, t in zip(got, want):
            np.testing.assert_allclose(
                g, t, atol=1e-4 if dtype == jnp.float32 else 0.3,
                rtol=1e-4 if dtype == jnp.float32 else 0.05)
    # rows that fill no tile, or sides off the 128 lanes: ragged_dot only
    monkeypatch.delenv("AF2_KERNEL_BACKEND_GROUPED_MATMUL")
    assert dispatch.resolve("grouped_matmul", platform="tpu", m=40, k=16, n=8,
                            groups=4) == "xla_ref"
    assert dispatch.resolve("grouped_matmul", platform="tpu", m=32768, k=2048,
                            n=768, groups=16) == "pallas_tpu"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,nk", [(32, 32), (24, 19)])  # 19: padded hop
def test_parity_merge_lse(monkeypatch, dtype, n, nk):
    """The ring hop's two arms compute one hop + log-space merge vs the
    stream_block recurrence over the same two K/V blocks — and both
    match full attention over the concatenated keys (the ring
    invariant)."""
    BH, dh = 4, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (BH, n, dh), dtype)
    k = jax.random.normal(ks[1], (BH, 2 * nk, dh), dtype)
    v = jax.random.normal(ks[2], (BH, 2 * nk, dh), dtype)
    k1, k2 = jnp.split(k, 2, axis=1)
    v1, v2 = jnp.split(v, 2, axis=1)
    bias = jnp.zeros((BH, nk), jnp.float32)
    scale = dh ** -0.5

    # pallas_tpu arm: per-hop fused (out, lse), merged in log space
    monkeypatch.setenv("AF2_KERNEL_BACKEND_MERGE_LSE", "pallas_tpu")
    assert dispatch.resolve("merge_lse", request="auto",
                            i=n, j=nk, dh=dh) == "pallas_tpu"
    o1, l1 = hop_attention_lse(q, k1, v1, bias, scale)
    o2, l2 = hop_attention_lse(q, k2, v2, bias, scale)
    out_kernel, _ = merge_lse(o1, l1, o2, l2)

    # xla_ref arm: the stream_block recurrence over the same hops
    monkeypatch.setenv("AF2_KERNEL_BACKEND_MERGE_LSE", "xla_ref")
    assert dispatch.resolve("merge_lse", request="auto",
                            i=n, j=nk, dh=dh) == "xla_ref"
    q4 = q.reshape(BH, n, 1, dh)
    m0 = jnp.full((BH, 1, n), float("-inf"), jnp.float32)
    l0 = jnp.zeros((BH, 1, n), jnp.float32)
    a0 = jnp.zeros((BH, 1, n, dh), jnp.float32)
    m, l, a = stream_block(q4, k1.reshape(BH, nk, 1, dh),
                           v1.reshape(BH, nk, 1, dh), bias, m0, l0, a0,
                           scale)
    m, l, a = stream_block(q4, k2.reshape(BH, nk, 1, dh),
                           v2.reshape(BH, nk, 1, dh), bias, m, l, a, scale)
    out_xla = (a / jnp.where(l > 0, l, 1.0)[..., None])[:, 0]

    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_kernel, np.float32),
                               np.asarray(out_xla, np.float32), atol=atol)

    # the ring invariant: both equal full attention over [k1; k2]
    full = np.asarray(blockwise_attention(
        q4, k.reshape(BH, 2 * nk, 1, dh), v.reshape(BH, 2 * nk, 1, dh),
        jnp.zeros((BH, 2 * nk), jnp.float32),
    )[:, :, 0], np.float32)
    np.testing.assert_allclose(np.asarray(out_xla, np.float32), full,
                               atol=atol)


def _ff_grads(fn, params, x, dy):
    """(out, dparams, dx) of one GEGLU call as float32 numpy."""
    out, vjp = jax.vjp(fn, params, x)
    dp, dx = vjp(dy.astype(out.dtype))
    leaves = [out, dx] + jax.tree_util.tree_leaves(dp)
    return [np.asarray(t, np.float32) for t in leaves]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(3, 50, 128), (2, 1100, 256)],
                         ids=["rows150-dim128", "rows2200-dim256"])
def test_parity_geglu_ff(monkeypatch, dtype, shape):
    """Kernel arm (interpret) == `_ff_core`, forward and the gradient of x,
    W_in, b_in, W_out and b_out, at mult 4: 150 rows fill one padded tile,
    2200 three tiles of 1024 (the last one padded), so the weights'
    gradients accumulate across grid steps."""
    from alphafold2_tpu.ops.feedforward import (feed_forward_apply,
                                                feed_forward_init)

    dim = shape[-1]
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    params = feed_forward_init(ks[0], dim)
    x = jax.random.normal(ks[1], shape, jnp.float32)
    dy = jax.random.normal(ks[2], shape, jnp.float32)
    rows = shape[0] * shape[1]
    got = {}
    for arm in ("pallas_tpu", "xla_ref"):
        monkeypatch.setenv("AF2_KERNEL_BACKEND_GEGLU_FF", arm)
        dispatch.reset_decisions()
        got[arm] = _ff_grads(
            lambda p, x: feed_forward_apply(p, x, dtype=dtype), params, x, dy)
        assert dispatch.decisions() == {
            f"geglu_ff -> {arm} @ rows={rows} dim={dim} hidden={4 * dim} "
            f"itemsize={jnp.dtype(dtype).itemsize} dropout=False "
            f"quantized=False": 1}
    # bf16: the XLA arm rounds the projection, the product and the bias
    # sums to bf16; the kernel keeps them in float32
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for k, x in zip(got["pallas_tpu"], got["xla_ref"]):
        assert k.shape == x.shape
        np.testing.assert_allclose(k, x, atol=tol * max(1.0, np.abs(x).max()))


def test_geglu_kernel_keeps_the_intermediate_in_float32(monkeypatch):
    """Against a float32 reference, the kernel at bf16 is at least as close
    as the XLA arm at bf16 (it rounds the intermediate only as the second
    matmul's operand), in the output and in every gradient."""
    from alphafold2_tpu.ops import geglu_kernel
    from alphafold2_tpu.ops.feedforward import _ff_core, feed_forward_init

    monkeypatch.setattr(geglu_kernel, "_TILE", 128)
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    params = feed_forward_init(ks[0], 128)
    x = jax.random.normal(ks[1], (300, 128), jnp.float32)
    dy = jax.random.normal(ks[2], (300, 128), jnp.float32)
    want = _ff_grads(lambda p, x: _ff_core(p, x, 0.0, None, jnp.float32),
                     params, x, dy)
    xla = _ff_grads(lambda p, x: _ff_core(p, x, 0.0, None, jnp.bfloat16),
                    params, x, dy)
    kernel = _ff_grads(lambda p, x: geglu_kernel.geglu_ff(p, x, jnp.bfloat16),
                       params, x, dy)
    for w, a, k in zip(want, xla, kernel):
        assert np.abs(k - w).max() <= np.abs(a - w).max()


def test_geglu_erf_matches_lax_erf():
    """Mosaic has no `lax.erf`: the kernels' float32 erf is XLA's rational
    form, within 2e-7 of `lax.erf` on a dense grid over [-6, 6]. Both are
    compiled at XLA's default optimization level: the suite's
    `jax_disable_most_optimizations` also stops the multiply-adds
    contracting to the fused ones XLA's own erf is written with, which
    moves the last bits (4.2e-7 at most)."""
    from alphafold2_tpu.ops.geglu_kernel import erf

    x = jnp.linspace(-6.0, 6.0, 1_200_001, dtype=jnp.float32)
    opts = {"xla_backend_optimization_level": 3,
            "xla_llvm_disable_expensive_passes": False}
    got, want = (jax.jit(f).lower(x).compile(compiler_options=opts)(x)
                 for f in (erf, jax.lax.erf))
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-7


_GEGLU_PAIR = dict(rows=1327104, dim=256, hidden=1024, itemsize=2,
                   dropout=False, quantized=False)


@pytest.mark.parametrize("platform,shapes,env,arm", [
    ("tpu", _GEGLU_PAIR, None, "pallas_tpu"),              # train_e2e's pair stream
    ("tpu", dict(_GEGLU_PAIR, rows=16384), None, "pallas_tpu"),  # the crossover
    ("tpu", dict(_GEGLU_PAIR, rows=16383), None, "xla_ref"),
    ("tpu", dict(_GEGLU_PAIR, rows=49152), None, "pallas_tpu"),  # its MSA stream
    ("tpu", dict(_GEGLU_PAIR, dropout=True), None, "xla_ref"),
    ("tpu", dict(_GEGLU_PAIR, quantized=True), None, "xla_ref"),
    ("tpu", dict(_GEGLU_PAIR, dim=96, hidden=384), None, "xla_ref"),  # off the lanes
    # the VMEM plan fits at bf16 and not at float32
    ("tpu", dict(_GEGLU_PAIR, dim=640, hidden=2560), None, "pallas_tpu"),
    ("tpu", dict(_GEGLU_PAIR, dim=640, hidden=2560, itemsize=4), None,
     "xla_ref"),
    ("cpu", _GEGLU_PAIR, None, "xla_ref"),
    ("tpu", _GEGLU_PAIR, {"AF2_KERNEL_BACKEND_GEGLU_FF": "off"}, "xla_ref"),
    ("cpu", _GEGLU_PAIR, {"AF2_KERNEL_BACKEND_GEGLU_FF": "pallas_tpu"},
     "pallas_tpu"),
], ids=["pair", "crossover", "under", "msa", "dropout", "int8", "dim96", "dim640-bf16",
        "dim640-f32", "cpu", "off", "forced"])
def test_geglu_ff_choice(monkeypatch, platform, shapes, env, arm):
    for name, value in (env or {}).items():
        monkeypatch.setenv(name, value)
    assert dispatch.resolve("geglu_ff", platform=platform, **shapes) == arm


@pytest.mark.parametrize("what", ["dropout", "int8"])
def test_geglu_ff_dropout_and_int8_take_the_xla_arm(monkeypatch, what):
    """A live dropout and the int8 serving tree keep the XLA arm, even
    where auto would take the kernel; forcing the kernel there fails
    loudly."""
    from alphafold2_tpu.ops import feedforward
    from alphafold2_tpu.ops.quant import quantize_tree

    params = feedforward.feed_forward_init(jax.random.PRNGKey(14), 128)
    x = jax.random.normal(jax.random.PRNGKey(15), (2, 64, 128))
    kw = {}
    if what == "dropout":
        kw = dict(dropout_rate=0.1, rng=jax.random.PRNGKey(16))
    else:
        params = quantize_tree(params, lambda path, w: True)
        assert "qw" in params["proj_in"] and "qw" in params["proj_out"]
    monkeypatch.setattr(dispatch, "_GEGLU_KERNEL_MIN_ROWS", 1)
    monkeypatch.setattr(dispatch, "_platform", lambda: "tpu")
    dispatch.reset_decisions()
    out = feedforward.feed_forward_apply(params, x, **kw)
    assert out.shape == x.shape
    [key] = [k for k in dispatch.decisions() if k.startswith("geglu_ff")]
    assert key.startswith(
        "geglu_ff -> xla_ref @ rows=128 dim=128 hidden=512 itemsize=4")
    assert f"{'dropout' if what == 'dropout' else 'quantized'}=True" in key
    # the same shape without dropout and with float weights takes the kernel
    assert dispatch.resolve("geglu_ff", rows=128, dim=128, hidden=512,
                            itemsize=4, dropout=False,
                            quantized=False) == "pallas_tpu"
    with pytest.raises(ValueError, match="does not support"):
        feedforward.feed_forward_apply(params, x, use_kernel=True, **kw)


# ---------------------------------------------------------------------------
# resolution semantics
# ---------------------------------------------------------------------------


def test_registry_shape():
    assert dispatch.ops() == ("flash_attention", "fused_attention",
                              "quant_matmul", "sparse_attention",
                              "merge_lse", "grouped_matmul", "geglu_ff")
    for op in dispatch.ops():
        spec = dispatch.get(op)
        # two arms an op: the kernel, and the reference every platform
        # that is not a TPU resolves to
        assert spec.arm_names() == ("pallas_tpu", "xla_ref")
        assert spec.parity_test.startswith("test_parity_")
    with pytest.raises(ValueError, match="unknown dispatch op"):
        dispatch.get("nonesuch")


def test_caller_forcing_wins():
    # True -> kernel arm anywhere; False -> xla_ref anywhere
    assert dispatch.resolve("flash_attention", request=True,
                            platform="cpu", i=16, j=16, dh=8) == "pallas_tpu"
    assert dispatch.resolve("flash_attention", request=False,
                            platform="tpu", i=16, j=1 << 20,
                            dh=64) == "xla_ref"
    with pytest.raises(ValueError, match="use_kernel must be"):
        dispatch.resolve("flash_attention", request="banana",
                         platform="cpu", i=16, j=16, dh=8)


def test_forced_unsupported_raises():
    with pytest.raises(ValueError, match="flash kernel does not support"):
        dispatch.resolve("flash_attention", request=True, platform="cpu",
                         i=16, j=16, dh=7)
    with pytest.raises(ValueError, match="quant kernel does not support"):
        dispatch.resolve("quant_matmul", request=True, platform="cpu",
                         m=8, k=16, n=8, x_dtype=jnp.float16)


def test_env_override_precedence(monkeypatch):
    shapes = dict(i=128, j=128, dh=64)
    # global forces every op
    monkeypatch.setenv("AF2_KERNEL_BACKEND", "pallas_tpu")
    assert dispatch.resolve("flash_attention", platform="cpu",
                            **shapes) == "pallas_tpu"
    # per-op wins over global
    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "xla_ref")
    assert dispatch.resolve("flash_attention", platform="cpu",
                            **shapes) == "xla_ref"
    assert dispatch.resolve("merge_lse", platform="cpu",
                            **shapes) == "pallas_tpu"  # global still holds
    # off == the xla_ref arm; auto == back to the heuristic
    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "off")
    assert dispatch.resolve("flash_attention", platform="tpu", i=128,
                            j=1 << 20, dh=64) == "xla_ref"
    monkeypatch.setenv("AF2_KERNEL_BACKEND", "auto")
    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "auto")
    assert dispatch.resolve("flash_attention", platform="cpu",
                            **shapes) == "xla_ref"
    # an explicit per-op "auto" RESTORES the heuristic under a global
    # override (the combination per-op-wins exists for)
    monkeypatch.setenv("AF2_KERNEL_BACKEND", "pallas_tpu")
    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "auto")
    assert dispatch.resolve("flash_attention", platform="cpu",
                            **shapes) == "xla_ref"   # cpu heuristic
    assert dispatch.resolve("merge_lse", platform="cpu",
                            **shapes) == "pallas_tpu"  # global still forces
    # unknown arm names fail loudly, listing the registered arms
    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "cuda12")
    with pytest.raises(ValueError, match="unknown backend arm"):
        dispatch.resolve("flash_attention", platform="cpu", **shapes)


def test_env_forcing_unsupported_shape_raises(monkeypatch):
    monkeypatch.setenv("AF2_KERNEL_BACKEND", "pallas_tpu")
    with pytest.raises(ValueError, match="does not support"):
        dispatch.resolve("flash_attention", platform="cpu",
                         i=16, j=16, dh=7)


# The decision table of "which attention core runs", in one place. A row
# is (platform, env, op, shapes) -> (arm, form): the arm is what
# ops/dispatch.py resolves, the form what ops/flash_kernel.py makes of
# the shape (`rows_plan` / `causal_plan`; None where the kernel does not
# run or the op has one form). Shapes: the training cell's trunk (config
# 5, crop 384: the pair stream's axial self-attention, the two aligned
# crosses), the serving engine's largest bucket, the dispatcher's probe,
# and the decoder cell's causal core (32 heads of 192 / 128).
_PAIR_AXIAL = dict(i=1152, j=1152, dh=64)
_PROBE = dict(i=1152, j=4096, dh=64)
_CAUSAL_8K = dict(i=8192, j=8192, dh=192, dv=128, causal=True)
_QUANT = dict(m=4096, k=512, n=512, x_dtype="float32")
_CELL_PLAN = {"g": 2, "qb": 1024, "kb": 256, "tiles": 36, "window": None}
_BELOW_CROSSOVER = [dict(i=3456, j=32, dh=64), dict(i=128, j=864, dh=64),
                    dict(i=384, j=384, dh=64)]


def _row(platform, op, shapes, arm, form=None, env=None):
    tag = "-".join(f"{k}{v}" for k, v in shapes.items()
                   if k in ("i", "j", "dh", "n"))
    return pytest.param(platform, env, op, shapes, arm, form,
                        id=f"{platform}{'-off' if env else ''}-{op}-{tag}")


_CORE_CHOICE = [
    # the measured path of train_e2e: the whole-row form
    _row("tpu", "flash_attention", _PAIR_AXIAL, "pallas_tpu", "whole-row"),
    # past the whole-row form's 2048 keys the kernel streams
    _row("tpu", "flash_attention", _PROBE, "pallas_tpu", "streaming"),
    # the measured path of train_lm_moe_8k: the triangular grid
    _row("tpu", "flash_attention", _CAUSAL_8K, "pallas_tpu", _CELL_PLAN),
    # a row whose resident dq does not fit (past 14 336 at 192 / 128)
    _row("tpu", "flash_attention", dict(_CAUSAL_8K, i=16384, j=16384),
         "xla_ref"),
    # a head size off the sublanes: `supported` says no
    _row("tpu", "flash_attention", dict(i=1152, j=1152, dh=60), "xla_ref"),
    _row("tpu", "fused_attention", _PAIR_AXIAL, "pallas_tpu"),
    _row("tpu", "merge_lse", _PAIR_AXIAL, "pallas_tpu"),
    # one key short of the measured crossover
    *[_row("tpu", op, dict(i=1152, j=1151, dh=64), "xla_ref")
      for op in ("flash_attention", "fused_attention", "merge_lse")],
    *[_row("tpu", "flash_attention", s, "xla_ref") for s in _BELOW_CROSSOVER],
    *[_row("cpu", "flash_attention", s, "xla_ref")
      for s in [_PAIR_AXIAL] + _BELOW_CROSSOVER],
    # a platform that is not a TPU has the reference arm, whatever it is
    *[_row(platform, op, shapes, "xla_ref")
      for platform in ("gpu", "cuda")
      for op, shapes in (("flash_attention", _PROBE),
                         ("quant_matmul", _QUANT))],
    # "no Pallas anywhere", at shapes where auto takes the kernel
    *[_row("tpu", op, shapes, "xla_ref", env={"AF2_KERNEL_BACKEND": "off"})
      for op, shapes in (("flash_attention", _PROBE),
                         ("fused_attention", _PROBE),
                         ("quant_matmul", _QUANT),
                         ("sparse_attention", dict(n=8192)),
                         ("merge_lse", _PAIR_AXIAL),
                         ("grouped_matmul",
                          dict(m=32768, k=2048, n=768, groups=16)))],
]


def _kernel_form(op, arm, s, h):
    """What ops/flash_kernel.py makes of a dense or causal call the
    kernel arm took."""
    from alphafold2_tpu.ops import flash_kernel

    if op != "flash_attention" or arm != "pallas_tpu":
        return None
    if s.get("causal"):
        plan = flash_kernel.causal_plan(s["i"], h, s["dh"], s["dv"])
        return {k: v for k, v in plan._asdict().items() if k != "vmem"}
    return ("streaming" if flash_kernel.rows_plan(s["i"], s["j"], h, s["dh"])
            is None else "whole-row")


@pytest.mark.parametrize("platform,env,op,shapes,arm,form", _CORE_CHOICE)
def test_attention_core_choice(monkeypatch, platform, env, op, shapes, arm,
                               form):
    if env:  # the row means something: without it auto takes the kernel
        assert dispatch.resolve(op, platform=platform,
                                **shapes) == "pallas_tpu"
        for name, value in env.items():
            monkeypatch.setenv(name, value)
    assert dispatch.resolve(op, platform=platform, **shapes) == arm
    heads = 32 if shapes.get("causal") else 8
    assert _kernel_form(op, arm, shapes, heads) == form


def test_decisions_tally_counts_what_a_traced_trunk_resolved():
    """`decisions()` after tracing a toy trunk: one entry a distinct
    (op, arm, shapes), counting every call site; forced requests and the
    introspection helpers add nothing."""
    from alphafold2_tpu.models import (Alphafold2Config, alphafold2_apply,
                                       alphafold2_init)

    dispatch.reset_decisions()
    assert dispatch.decisions() == {}
    dispatch.resolution_tag()
    dispatch.resolution_table()
    dispatch.resolve("flash_attention", request=False, platform="cpu",
                     i=8, j=8, dh=8)
    assert dispatch.decisions() == {}

    cfg = Alphafold2Config(dim=16, depth=2, heads=2, dim_head=8,
                           max_seq_len=16, attn_flash=True)
    params = jax.eval_shape(lambda k: alphafold2_init(k, cfg),
                            jax.random.PRNGKey(0))
    jax.eval_shape(lambda p, s, m: alphafold2_apply(p, cfg, s, msa=m), params,
                   jax.ShapeDtypeStruct((1, 8), jnp.int32),
                   jax.ShapeDtypeStruct((1, 3, 8), jnp.int32))
    tally = dispatch.decisions()
    flash = {k: n for k, n in tally.items() if k.startswith("flash_attention")}
    # every flash call site of the toy trunk resolved to the XLA arm here;
    # the pair stream's axial passes see i = j = 8 at dh = 8
    assert flash and all(" -> xla_ref @ " in k for k in flash)
    assert flash["flash_attention -> xla_ref @ i=8 j=8 dh=8"] >= 2 * cfg.depth
    assert sum(tally.values()) >= sum(flash.values())
    # a second trace of the same program adds to the same entries
    dispatch.resolve("flash_attention", platform="tpu", **_PAIR_AXIAL)
    assert dispatch.decisions()[
        "flash_attention -> pallas_tpu @ i=1152 j=1152 dh=64"] == 1
    dispatch.reset_decisions()
    assert dispatch.decisions() == {}


def test_auto_heuristics_per_platform():
    long_j = dict(i=1152, j=4096, dh=64)
    short_j = dict(i=1152, j=864, dh=64)
    assert dispatch.resolve("flash_attention", platform="tpu",
                            **long_j) == "pallas_tpu"
    assert dispatch.resolve("flash_attention", platform="tpu",
                            **short_j) == "xla_ref"  # measured crossover
    assert dispatch.resolve("flash_attention", platform="cpu",
                            **long_j) == "xla_ref"
    assert dispatch.resolve("sparse_attention", platform="tpu",
                            n=8192) == "pallas_tpu"
    assert dispatch.resolve("sparse_attention", platform="tpu",
                            n=2048) == "xla_ref"
    assert dispatch.resolve("quant_matmul", platform="tpu", m=64, k=64,
                            n=64, x_dtype=jnp.float32) == "pallas_tpu"
    assert dispatch.resolve("quant_matmul", platform="cpu", m=64, k=64,
                            n=64, x_dtype=jnp.float32) == "xla_ref"


def test_kill_switches_still_downgrade_auto(monkeypatch):
    """The one kill-switch is `off` on the override channel: global for
    every op, per-op for one; a caller's forcing still wins."""
    long_j = dict(i=1152, j=4096, dh=64)
    quant = dict(m=64, k=64, n=64, x_dtype=jnp.float32)
    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "off")
    assert dispatch.resolve("flash_attention", platform="tpu",
                            **long_j) == "xla_ref"
    assert dispatch.resolve("quant_matmul", platform="tpu",
                            **quant) == "pallas_tpu"  # per-op: the others stay
    monkeypatch.setenv("AF2_KERNEL_BACKEND", "off")
    assert dispatch.resolve("sparse_attention", platform="tpu",
                            n=8192) == "xla_ref"
    assert dispatch.resolve("quant_matmul", platform="tpu",
                            **quant) == "xla_ref"
    assert dispatch.resolve("flash_attention", request=True,
                            platform="cpu", i=16, j=16, dh=8) == "pallas_tpu"


def test_resolution_tag_and_table(monkeypatch):
    tag = dispatch.resolution_tag(platform="cpu")
    assert tag.startswith("dispatch[cpu](")
    for op in dispatch.ops():
        assert f"{op}=xla_ref" in tag
    # env overrides change the tag (the serving aliasing lever)
    monkeypatch.setenv("AF2_KERNEL_BACKEND", "pallas_tpu")
    assert dispatch.resolution_tag(platform="cpu") != tag
    monkeypatch.delenv("AF2_KERNEL_BACKEND")
    rows = dispatch.resolution_table(platform="tpu")
    assert [r[0] for r in rows] == list(dispatch.ops())
    by_op = {r[0]: r for r in rows}
    _, probe, supp, resolved = by_op["flash_attention"]
    assert supp["xla_ref"] and supp["pallas_tpu"]
    assert resolved == "pallas_tpu"  # long-j probe on TPU
    # a malformed forced env shows up as an ERROR row, not a crash
    monkeypatch.setenv("AF2_KERNEL_BACKEND", "cuda12")
    rows = dispatch.resolution_table(platform="cpu")
    assert all(r[3].startswith("ERROR:") for r in rows)


def test_check_cli_output_pinned(capsys):
    assert dispatch.main(["--check", "--platform", "cpu"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("kernel dispatch registry @ platform=cpu\n")
    for op in dispatch.ops():
        assert op in out
    # two arms a row, the same two for every op
    assert out.count("pallas_tpu=yes xla_ref=yes  -> ") == len(dispatch.ops())
    assert out.count("-> xla_ref") == len(dispatch.ops())
    assert "tag: dispatch[cpu](" in out


# ---------------------------------------------------------------------------
# knobs: strict parsing + the generated docs table
# ---------------------------------------------------------------------------


def test_knob_strict_values(monkeypatch):
    monkeypatch.setenv("AF2_COMM_OVERLAP", "flase")  # the typo
    with pytest.raises(ValueError, match="AF2_COMM_OVERLAP"):
        knobs.comm_overlap_enabled()
    monkeypatch.setenv("AF2_COMM_OVERLAP", "off")
    assert not knobs.comm_overlap_enabled()
    monkeypatch.delenv("AF2_COMM_OVERLAP")
    assert knobs.comm_overlap_enabled()  # default ON
    monkeypatch.setenv("AF2_AUTO_INIT", "yes")
    assert knobs.auto_init()
    monkeypatch.setenv("AF2_NUM_PROCESSES", "many")
    with pytest.raises(ValueError, match="AF2_NUM_PROCESSES"):
        knobs.num_processes()
    monkeypatch.setenv("AF2_PALLAS_INTERPRET", "maybe")
    with pytest.raises(ValueError, match="AF2_PALLAS_INTERPRET"):
        knobs.pallas_interpret_override()
    # the override channel hands an arm name on verbatim: the dispatcher
    # is what knows the arms, and refuses a name it does not have
    monkeypatch.setenv("AF2_KERNEL_BACKEND_QUANT_MATMUL", "Force")
    assert knobs.kernel_backend_override("quant_matmul") == "force"
    with pytest.raises(ValueError, match="unknown backend arm"):
        dispatch.resolve("quant_matmul", platform="cpu", m=8, k=16, n=8,
                         x_dtype=jnp.float32)


def test_knob_registry_covers_every_accessor():
    """`KNOBS` is the eight names the package reads, no more: every
    AF2_* name that ops/knobs.py mentions at all (an accessor's read, a
    docstring) is one of them, or a per-op spelling of the override."""
    import inspect
    import re

    names = [k.name for k in knobs.KNOBS]
    assert names == ["AF2_KERNEL_BACKEND", "AF2_KERNEL_BACKEND_<OP>",
                     "AF2_PALLAS_INTERPRET", "AF2_COMM_OVERLAP",
                     "AF2_COORDINATOR", "AF2_NUM_PROCESSES",
                     "AF2_PROCESS_ID", "AF2_AUTO_INIT"]
    mentioned = set(re.findall(r"AF2_[A-Z_]*[A-Z]", inspect.getsource(knobs)))
    stray = {n for n in mentioned
             if n not in names and not n.startswith("AF2_KERNEL_BACKEND_")}
    assert not stray, stray


def test_knob_table_in_docs_is_generated():
    """docs/OPERATIONS.md's env-knob block must EQUAL generate_table():
    the table is generated, not hand-maintained — regenerate with
    `python -m alphafold2_tpu.ops.knobs` after editing the registry."""
    path = os.path.join(REPO_ROOT, "docs", "OPERATIONS.md")
    text = open(path).read()
    begin, end = "<!-- af2knobs:begin -->", "<!-- af2knobs:end -->"
    assert begin in text and end in text, "knob table markers missing"
    block = text.split(begin, 1)[1].split(end, 1)[0].strip()
    assert block == knobs.generate_table().strip()


# ---------------------------------------------------------------------------
# the af2lint dispatch pass
# ---------------------------------------------------------------------------


class _FakeSpec:
    def __init__(self, name, arms, parity_test):
        self.name = name
        self._arms = arms
        self.parity_test = parity_test

    def arm_names(self):
        return tuple(self._arms)


class TestDispatchLint:
    def test_repo_is_clean(self):
        from alphafold2_tpu.analysis.dispatch_lint import run

        findings = run(REPO_ROOT)
        assert findings == [], [f.render() for f in findings]

    def test_pass_registered(self):
        from alphafold2_tpu.analysis import PASSES, run_passes

        assert "dispatch" in PASSES
        assert run_passes(REPO_ROOT, select=("dispatch",)) == []

    def test_missing_xla_ref_arm_fires(self, tmp_path):
        from alphafold2_tpu.analysis.dispatch_lint import check_registry

        reg = [_FakeSpec("my_op", ("pallas_tpu",), "test_parity_flash_attention")]
        codes = {f.code for f in check_registry(
            REPO_ROOT, registry=reg)}
        assert codes == {"DISPATCH001"}

    def test_unregistered_parity_test_fires(self):
        from alphafold2_tpu.analysis.dispatch_lint import check_registry

        reg = [_FakeSpec("my_op", ("pallas_tpu", "xla_ref"), ""),
               _FakeSpec("other", ("xla_ref",), "test_parity_nonesuch")]
        codes = sorted(f.code for f in check_registry(REPO_ROOT,
                                                      registry=reg))
        assert codes == ["DISPATCH002", "DISPATCH002"]

    def test_live_registry_parity_tests_exist(self):
        from alphafold2_tpu.analysis.dispatch_lint import check_registry

        assert check_registry(REPO_ROOT) == []

    def test_kernel_import_outside_ops_fires(self, tmp_path):
        from alphafold2_tpu.analysis.dispatch_lint import check_sources

        pkg = tmp_path / "alphafold2_tpu" / "parallel"
        pkg.mkdir(parents=True)
        bad = pkg / "rogue.py"
        bad.write_text(
            "from alphafold2_tpu.ops.flash_kernel import flash_attention_tpu\n"
            "from alphafold2_tpu.ops import sparse_kernel\n"
        )
        codes = [f.code for f in check_sources(tmp_path, files=[bad])]
        assert codes == ["DISPATCH003", "DISPATCH003"]

    def test_env_read_outside_knobs_fires(self, tmp_path):
        from alphafold2_tpu.analysis.dispatch_lint import check_sources

        pkg = tmp_path / "alphafold2_tpu" / "serving"
        pkg.mkdir(parents=True)
        bad = pkg / "rogue.py"
        bad.write_text(
            "import os\n"
            "A = os.environ.get('AF2_SOMETHING', '')\n"
            "B = os.getenv('AF2_OTHER')\n"
            "C = os.environ['AF2_THIRD']\n"
            "os.environ['AF2_WRITE_OK'] = '1'\n"   # writes are fine
            "D = os.environ.get('NOT_OURS')\n"     # non-AF2 is fine
        )
        codes = [f.code for f in check_sources(tmp_path, files=[bad])]
        assert codes == ["DISPATCH004", "DISPATCH004", "DISPATCH004"]

    def test_knobs_and_ops_are_exempt(self, tmp_path):
        from alphafold2_tpu.analysis.dispatch_lint import check_sources

        ops_dir = tmp_path / "alphafold2_tpu" / "ops"
        ops_dir.mkdir(parents=True)
        knobs_py = ops_dir / "knobs.py"
        knobs_py.write_text(
            "import os\nA = os.environ.get('AF2_SOMETHING', '')\n"
        )
        kernel_user = ops_dir / "flash.py"
        kernel_user.write_text(
            "from alphafold2_tpu.ops import flash_kernel\n"
        )
        assert check_sources(
            tmp_path, files=[knobs_py, kernel_user]) == []


# ---------------------------------------------------------------------------
# the cross-backend bench matrix contract (telemetry.check)
# ---------------------------------------------------------------------------


class TestPlatformQualifiedGate:
    def test_rows_qualify_by_platform_and_arm(self):
        from alphafold2_tpu.telemetry.check import load_metrics

        got = load_metrics({
            "bench": "disp_flash_attention_xla_ref",
            "result": {"op": "flash_attention", "backend_arm": "xla_ref",
                       "platform": "cpu", "sec_per_iter": 0.35},
        })
        assert got == {
            "disp_flash_attention_xla_ref.cpu.xla_ref.sec_per_iter": 0.35,
        }

    def test_cpu_row_cannot_gate_against_tpu_row(self, tmp_path):
        """THE satellite pin: the same leg measured on two platforms
        shares no metric name, so telemetry.check can never diff a CPU
        row against a TPU baseline (and vice versa)."""
        from alphafold2_tpu.telemetry.check import check, load_metrics

        def sweep(name, platform, arm, secs):
            p = tmp_path / name
            p.write_text(json.dumps({
                "bench": "disp_flash_attention_xla_ref",
                "result": {"platform": platform, "backend_arm": arm,
                           "sec_per_iter": secs},
            }) + "\n")
            return str(p)

        cur = sweep("cur.jsonl", "cpu", "xla_ref", 99.0)  # 10x "slower"
        base = sweep("base.jsonl", "tpu", "pallas_tpu", 9.0)
        cur_m, base_m = load_metrics(cur), load_metrics(base)
        assert not (set(cur_m) & set(base_m))
        passed, rows = check(cur, base)
        assert passed and rows == []  # nothing comparable, nothing gated
        # same platform+arm DOES gate — the trajectory is per-backend
        base2 = sweep("base2.jsonl", "cpu", "xla_ref", 9.0)
        passed, rows = check(cur, base2)
        assert not passed
        assert rows[0]["metric"] == (
            "disp_flash_attention_xla_ref.cpu.xla_ref.sec_per_iter")

    def test_legacy_rows_keep_unqualified_names(self):
        from alphafold2_tpu.telemetry.check import load_metrics

        got = load_metrics({"bench": "e2e_auto",
                            "result": {"sec_per_step": 24.4}})
        assert got == {"e2e_auto.sec_per_step": 24.4}
        # rows recorded BEFORE the matrix carry platform alone (the
        # PR 8/11/12 chip-free legs): they must also keep their
        # historical names, or every published baseline of those legs
        # silently stops gating — qualification requires BOTH fields
        got = load_metrics({
            "bench": "featurize_overlap",
            "result": {"platform": "cpu",
                       "featurize_overlap_ratio": 2.19},
        })
        assert got == {"featurize_overlap.featurize_overlap_ratio": 2.19}


def test_serving_stats_surface_dispatch_tag():
    """The resolved-arm tag must be operator-visible (stats()) and part
    of the engine config tag — the full aliasing pin lives in
    tests/test_serving.py::test_config_tag_covers_backend_arm."""
    tag = dispatch.resolution_tag()
    assert tag.startswith("dispatch[")
    for op in dispatch.ops():
        assert f"{op}=" in tag

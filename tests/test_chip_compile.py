"""The main path's Pallas kernel compiled for a described (not attached)
TPU v5e at the training cell's real widths: Mosaic refuses here what it
would refuse on the chip (VMEM over the limit, a slice off the tiling),
at no chip time. Nothing runs, so this says nothing about results or
times. All such compiles live in this ONE file: the worker that gets it
loads the TPU's library, and only a fixture may describe the topology."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from alphafold2_tpu import compat
from alphafold2_tpu.ops import flash_kernel
from alphafold2_tpu.ops.flash import flash_attention

CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    try:
        topo = compat.describe_topology("tpu", "v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_mode(monkeypatch):
    """Trace the kernels for Mosaic, not for the interpreter, and keep the
    undeserializable TPU executables out of the compile cache."""
    monkeypatch.setenv("AF2_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _chunk(one_chip, B, i, j, h=8, dh=64):
    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # as the projections hand them over: heads still folded in the width
    return (sd((B, i, h * dh)), sd((B, j, h * dh)), sd((B, j, h * dh)),
            sd((B, j), jnp.float32))


@pytest.mark.parametrize("i,j,form", [(1152, 1152, "whole-row"),
                                      (1152, 4096, "streaming")])
def test_flash_kernel_compiles_for_v5e_at_real_widths(one_chip, compiled_mode,
                                                      i, j, form):
    """Forward and backward of one batch chunk: the pair stream's axial
    shape in the whole-row form (one backward kernel), a long-j shape in
    the streaming form (dq and dk/dv kernels)."""
    plan = flash_kernel.rows_plan(i, j, 8, 64)
    assert (plan is not None) == (form == "whole-row")
    args = _chunk(one_chip, 4, i, j)

    def fwd(q, k, v, bias):
        q, k, v = (t.reshape(*t.shape[:2], 8, 64) for t in (q, k, v))
        out = flash_attention(q, k, v, bias, use_kernel=True)
        return out.reshape(*out.shape[:2], 8 * 64)

    def loss(q, k, v, bias):
        return jnp.sum(fwd(q, k, v, bias).astype(jnp.float32))

    assert jax.jit(fwd).lower(*args).compile().as_text().count(CALL) == 1
    grad = jax.jit(jax.grad(loss, (0, 1, 2))).lower(*args).compile().as_text()
    assert grad.count(CALL) == (2 if form == "whole-row" else 3)
    if form == "whole-row":
        # q, k, v and the output keep the model's (B, n, h*dh) layout:
        # nothing transposes or copies them through HBM around the kernels
        assert " transpose(" not in grad and " copy(" not in grad


def test_causal_kernel_compiles_for_v5e_at_the_decoders_widths(one_chip,
                                                               compiled_mode):
    """The language-model cell's core, 2 x 8192 tokens, 32 heads of 192
    (q, k) and 128 (v): one forward and ONE backward kernel at the plan
    the shape gets, reading q, k, v as the projections hand them over."""
    B, n, h, dh, dv = 2, 8192, 32, 192, 128
    plan = flash_kernel.causal_plan(n, h, dh, dv)
    assert plan.g == 2 and plan.tiles == (n // plan.qb) * (n // plan.qb + 1) // 2

    def sd(width):
        return jax.ShapeDtypeStruct((B, n, h * width), jnp.bfloat16,
                                    sharding=one_chip)

    def fwd(q, k, v):
        out = flash_attention(q.reshape(B, n, h, dh), k.reshape(B, n, h, dh),
                              v.reshape(B, n, h, dv), causal=True,
                              use_kernel=True)
        return out.reshape(B, n, h * dv)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    args = (sd(dh), sd(dh), sd(dv))
    text = jax.jit(fwd).lower(*args).compile().as_text()
    assert text.count(CALL) == 1
    # nothing folds heads into the batch or pads around the kernel
    for op in (" transpose(", " copy(", " pad("):
        assert op not in text
    grad = jax.jit(jax.grad(loss, (0, 1, 2))).lower(*args).compile().as_text()
    assert grad.count(CALL) == 2
    assert " transpose(" not in grad and " pad(" not in grad


@pytest.mark.parametrize("window,tiles", [(1024, 15), (1000, 15), (1536, 21)],
                         ids=["the_cells", "no_multiple_of_the_block", "a_block_and_a_half"])
def test_windowed_causal_kernel_compiles_for_v5e_at_the_decoders_widths(
        one_chip, compiled_mode, window, tiles):
    """The `mellum` cell's window layers, 2 x 8192 tokens, 32 query heads
    over 4 key heads of 128: one forward and ONE backward kernel on the
    band's tiles (15 of the triangle's 36 at the published window), the
    sub-tiles the band's edge cuts sliced on the tiling."""
    B, n, h, hk, dh = 2, 8192, 32, 4, 128
    plan = flash_kernel.causal_plan(n, h, dh, dh, window=window)
    assert (plan.tiles, plan.window, plan.qb) == (tiles, window, 1024)
    assert flash_kernel.causal_plan(n, h, dh, dh).tiles == 36

    def sd(heads):
        return jax.ShapeDtypeStruct((B, n, heads * dh), jnp.bfloat16,
                                    sharding=one_chip)

    def fwd(q, k, v):
        out = flash_attention(q.reshape(B, n, h, dh), k.reshape(B, n, hk, dh),
                              v.reshape(B, n, hk, dh), causal=True, window=window,
                              use_kernel=True)
        return out.reshape(B, n, h * dh)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    args = (sd(h), sd(hk), sd(hk))
    assert jax.jit(fwd).lower(*args).compile().as_text().count(CALL) == 1
    grad = jax.jit(jax.grad(loss, (0, 1, 2))).lower(*args).compile().as_text()
    assert grad.count(CALL) == 2


def _copies_of(shape, text):
    """` copy(` operations of a compiled step's text whose result has `shape`."""
    dims = ",".join(map(str, shape)) + "]"
    return sum(" copy(" in line and dims in line.split(" copy(")[0]
               for line in text.splitlines())


def test_decoder_stack_keeps_the_cores_results_for_v5e(one_chip, compiled_mode,
                                                       monkeypatch):
    """The decoder's dense stack at the language-model cell's widths (2 x
    8192 tokens, hidden 2048, 32 heads of 192 / 128), two layers scanned:
    its gradient holds TWO calls of the causal core, the forward kernel in
    the forward scan and the backward kernel in the backward scan. A layer
    checkpoint that did not keep `out` and `lse` holds three (the forward
    again in the backward scan). The saved `out` reaches the backward
    kernel's wrapper and W_o's gradient as the stack's slice: no copy of
    its shape beyond those of v, dv and the cotangent, which a bare
    checkpoint has too."""
    from alphafold2_tpu.models import decoder

    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "pallas_tpu")
    B, n = 2, 8192
    cfg = decoder.DecoderConfig(
        vocab_size=128, hidden_size=2048, num_hidden_layers=2,
        first_k_dense_replace=2, num_attention_heads=32, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
        intermediate_size=6144, moe_intermediate_size=768, n_routed_experts=128,
        num_experts_per_tok=6, n_shared_experts=2, routed_scaling_factor=2.448,
        rope_theta=1e6)

    def sd(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)

    layers = jax.tree_util.tree_map(sd, jax.eval_shape(
        lambda: decoder.decoder_init(jax.random.PRNGKey(0), cfg))["dense"])
    h = sd(jax.ShapeDtypeStruct((B, n, cfg.hidden_size), jnp.bfloat16))

    def grad_text():
        def loss(layers, h):
            out, _ = decoder._stack(
                lambda lp, c: decoder._layer(lp, c, cfg, False), h, layers)
            return jnp.sum(out.astype(jnp.float32))

        return jax.jit(jax.grad(loss, (0, 1))).lower(layers, h).compile().as_text()

    kept = grad_text()
    monkeypatch.setattr(
        decoder, "_checkpointed_layer",
        lambda layer: jax.checkpoint(lambda h, lp: layer(lp, h)))
    bare = grad_text()
    assert (kept.count(CALL), bare.count(CALL)) == (2, 3)
    out = (B, n, cfg.num_attention_heads * cfg.v_head_dim)
    assert _copies_of(out, kept) <= _copies_of(out, bare)


def test_batch_chunks_keep_the_whole_row_cores_results_for_v5e(
        one_chip, compiled_mode, monkeypatch):
    """One axial pass of the pair stream at the training cell's widths (dim
    256, 8 heads of 64, rows of 1152) over two batch chunks of 96: its
    gradient holds TWO calls of the whole-row core, the forward kernel in
    the forward map and the backward kernel in the backward map. A chunk
    checkpoint that did not keep `out` and `lse` holds three (the forward
    again in the backward map). The saved `out` comes back as the stack's
    slice: no more copies of its shape than a bare checkpoint has."""
    from alphafold2_tpu.ops import attention

    monkeypatch.setenv("AF2_KERNEL_BACKEND_FLASH_ATTENTION", "pallas_tpu")
    chunk, n = 96, 1152
    cfg = attention.AttentionConfig(dim=256, heads=8, dim_head=64,
                                    dtype=jnp.bfloat16, batch_chunk=chunk)

    def sd(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(sd, jax.eval_shape(
        lambda: attention.attention_init(jax.random.PRNGKey(0), cfg)))
    x = sd(jax.ShapeDtypeStruct((2 * chunk, n, cfg.dim), jnp.bfloat16))

    def grad_text():
        def loss(params, x):
            # squared, so that the backward pass needs the forward map's result
            return jnp.sum(attention.attention_apply(params, cfg, x).astype(jnp.float32) ** 2)

        return jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile().as_text()

    kept = grad_text()
    monkeypatch.setattr(attention, "_checkpointed_chunk", jax.checkpoint)
    bare = grad_text()
    assert (kept.count(CALL), bare.count(CALL)) == (2, 3)
    out = (chunk, n, cfg.inner_dim)
    assert _copies_of(out, kept) <= _copies_of(out, bare)


def test_expert_loop_compiles_once_a_direction_for_v5e(one_chip, compiled_mode,
                                                       monkeypatch):
    """The expert layer at the sliding-window cell's widths (2 x 8192
    tokens of 2304, top-8 of 64, 16 experts of 896 held) under a
    layer-like checkpoint: Mosaic takes the megablox kernels inside a loop
    whose bound is data, the loop has no branch, and the compiled gradient
    holds the grouped kernels of ONE forward loop (3) and ONE backward loop
    (its block's recomputation, the transposes and `tgmm`: 9), none for
    the checkpoint's second forward, whose result nothing reads."""
    from alphafold2_tpu.ops import moe

    monkeypatch.setenv("AF2_KERNEL_BACKEND_GROUPED_MATMUL", "pallas_tpu")
    n, d, f, held = 2 * 8192, 2304, 896, (0, 16)

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {
        "router": {"w": sd((d, 64))},
        "experts": {"gate": {"w": sd((16, d, f))}, "up": {"w": sd((16, d, f))},
                    "down": {"w": sd((16, f, d))}}}

    def layer(p, h):
        routing = moe.route_softmax(moe.router_logits(p, h), None, 8, norm_topk=True)
        return h + moe.moe_apply(p, h, routing, held=held)[0]

    def loss(p, h):
        return jnp.sum(jax.checkpoint(layer)(p, h).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        params, sd((n, d), jnp.bfloat16)).compile().as_text()
    assert text.count(CALL) == 12
    assert " conditional(" not in text


def test_geglu_kernels_compile_for_v5e_at_the_pair_streams_width(one_chip,
                                                                 compiled_mode):
    """The pair stream's feed-forward in `train_e2e`, 1152^2 rows of 256
    through mult 4: one forward kernel, and a gradient of ONE forward and
    ONE backward kernel, with no transpose of the rows around them."""
    from alphafold2_tpu.ops import geglu_kernel

    rows, d, h = 1152 * 1152, 256, 1024
    assert geglu_kernel.plan(rows, d, h, 2) is not None

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"proj_in": {"w": sd((d, 2 * h)), "b": sd((2 * h,))},
              "proj_out": {"w": sd((h, d)), "b": sd((d,))}}
    x = sd((rows, d), jnp.bfloat16)

    def fwd(p, x):
        return geglu_kernel.geglu_ff(p, x, jnp.bfloat16)

    def loss(p, x):
        return jnp.sum(jnp.square(fwd(p, x).astype(jnp.float32)))

    assert jax.jit(fwd).lower(params, x).compile().as_text().count(CALL) == 1
    grad = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile().as_text()
    assert grad.count(CALL) == 2
    assert not any(" transpose(" in line and f"[{rows}," in line
                   for line in grad.splitlines())
    assert " pad(" not in grad

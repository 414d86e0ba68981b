"""Plain reference of the Alphafold2 trunk and distogram head.

Straightforward `jax.numpy` in float32 with every contraction at
`Precision.HIGHEST` (or `HIGH`, see `set_precision`); no kernels, no streaming softmax, no compute-dtype
casts. It imports nothing of the program: it reads a parameter tree of
the published layout (token/position tables, per-layer blocks of
pre-norm axial attention, aligned cross-attention with strided key/value
compression, GEGLU feed-forward, symmetrised distogram head) and follows
the layer equations of `alphafold2-pytorch` (the reversible two-stream
layer of reversible.py).

Big intermediates are bounded by mapping whole attention/feed-forward
calls over blocks of their leading (folded) axis, each block under
`jax.checkpoint`: that changes where memory is spent, not one number.

`q` is the operand rounding of the control (`lowprec.py`): it is applied
to both operands of every contraction. `None` is the reference itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# precision of the trunk's, head's and refiner's contractions: `highest`
# (six bfloat16 passes on a TPU) unless a configuration's file asks for
# `high` (three passes, still float32 to about 1e-6) to keep the check short
PRECISION = {"contract": jax.lax.Precision.HIGHEST}


def set_precision(name: str):
    """Before the first trace of a process: `highest` or `high`."""
    PRECISION["contract"] = {"highest": jax.lax.Precision.HIGHEST,
                             "high": jax.lax.Precision.HIGH}[name]


def _q(q, t):
    return t if q is None else q(t)


def mm(x, w, q=None):
    return jnp.matmul(_q(q, x), _q(q, w), precision=PRECISION["contract"])


def ein(spec, a, b, q=None):
    return jnp.einsum(spec, _q(q, a), _q(q, b), precision=PRECISION["contract"])


def layer_norm(p, x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def linear(p, x, q=None):
    y = mm(x, p["w"], q)
    return y + p["b"] if "b" in p else y


def blocked(fn, x_args, block):
    """fn(*x_args) with every x_arg cut along axis 0 into blocks of at
    most `block` rows (the largest divisor of the axis that fits), each
    block under checkpoint."""
    n = x_args[0].shape[0]
    if not block or n <= block:
        return fn(*x_args)
    while n % block:
        block -= 1
    cut = [a.reshape((n // block, block) + a.shape[1:]) for a in x_args]
    out = jax.lax.map(jax.checkpoint(lambda args: fn(*args)), tuple(cut))
    return out.reshape((n,) + out.shape[2:])


# --- attention ---------------------------------------------------------------

def _heads(t, heads):
    b, n, inner = t.shape
    return t.reshape(b, n, heads, inner // heads)


def compress_kv(p, t, heads, ratio, q=None):
    """Strided grouped convolution over the key axis: windows of `ratio`
    keys, one feature group per head. t: (b, j, inner)."""
    b, j, inner = t.shape
    if j % ratio:
        raise ValueError("the reference takes key lengths that divide")
    dh = inner // heads
    win = t.reshape(b, j // ratio, ratio, heads, dh)
    w = p["w"].reshape(ratio, dh, heads, dh)  # (k, c_in, g, c_out)
    out = ein("btkgc,kcgo->btgo", win, w, q)
    return out.reshape(b, j // ratio, inner) + p["b"]


def attention(p, x, context=None, *, heads, ratio=1, q=None):
    """Softmax attention on full masks. x (b, i, d); context (b, j, d) or
    None (self)."""
    ctx = x if context is None else context
    qh = linear(p["to_q"], x, q)
    kv = linear(p["to_kv"], ctx, q)
    k, v = jnp.split(kv, 2, axis=-1)
    if ratio > 1 and context is not None:
        k = compress_kv(p["compress"], k, heads, ratio, q)
        v = compress_kv(p["compress"], v, heads, ratio, q)
    qh, k, v = _heads(qh, heads), _heads(k, heads), _heads(v, heads)
    scale = qh.shape[-1] ** -0.5
    logits = ein("bihd,bjhd->bhij", qh, k, q) * scale
    attn = jax.nn.softmax(logits, axis=-1)
    out = ein("bhij,bjhd->bihd", attn, v, q)
    return linear(p["to_out"], out.reshape(out.shape[0], out.shape[1], -1), q)


def tied_row_attention(p, x, *, heads, q=None):
    """Row attention of the MSA with logits shared by all rows.
    x (b, r, n, d): attend along n; logits summed over r, scaled r^-1/2."""
    b, r, n, d = x.shape
    qh = linear(p["to_q"], x, q)
    kv = linear(p["to_kv"], x, q)
    k, v = jnp.split(kv, 2, axis=-1)
    shape = (b, r, n, heads, qh.shape[-1] // heads)
    qh, k, v = qh.reshape(shape), k.reshape(shape), v.reshape(shape)
    scale = shape[-1] ** -0.5 * r ** -0.5
    logits = ein("brihd,brjhd->bhij", qh, k, q) * scale
    attn = jax.nn.softmax(logits, axis=-1)
    out = ein("bhij,brjhd->brihd", attn, v, q)
    return linear(p["to_out"], out.reshape(b, r, n, -1), q)


def axial_attention(p, x, *, heads, tie_row=False, block=0, q=None):
    """Two passes over a (b, h, w, d) grid, summed: along h with w folded
    into the batch (`attn_width`), along w with h folded (`attn_height`)."""
    b, hh, ww, d = x.shape
    col_x = jnp.swapaxes(x, 1, 2).reshape(b * ww, hh, d)
    col = blocked(lambda t: attention(p["attn_width"], t, heads=heads, q=q),
                  (col_x,), block)
    if tie_row:
        row = tied_row_attention(p["attn_height"], x, heads=heads, q=q)
    else:
        row = blocked(lambda t: attention(p["attn_height"], t, heads=heads, q=q),
                      (x.reshape(b * hh, ww, d),), block)
    col = jnp.swapaxes(col.reshape(b, ww, hh, d), 1, 2)
    return col + row.reshape(b, hh, ww, d)


def feed_forward(p, x, *, block=0, q=None):
    """GEGLU: Linear(d, 8d) -> value * gelu(gate) -> Linear(4d, d)."""
    def core(t):
        y = linear(p["proj_in"], t, q)
        value, gate = jnp.split(y, 2, axis=-1)
        return linear(p["proj_out"], value * jax.nn.gelu(gate, approximate=False), q)
    flat = x.reshape(-1, x.shape[-1])
    return blocked(core, (flat,), block).reshape(x.shape)


# --- the blocks of a layer (each is added to its stream) ------------------------

def pair_self(p, x, hp, q):
    return axial_attention(p["attn"], layer_norm(p["norm"], x),
                           heads=hp["heads"], block=hp["attn_block"], q=q)


def msa_self(p, m, hp, q):
    return axial_attention(p["attn"], layer_norm(p["norm"], m),
                           heads=hp["heads"], tie_row=hp["tie_row"], q=q)


def ff_block(p, t, hp, q):
    return feed_forward(p["ff"], layer_norm(p["norm"], t),
                        block=hp["ff_block"], q=q)


def _fold_pair(x, c):
    b, n, _, d = x.shape
    f = n // c
    return (x.reshape(b, n, c, f, d).transpose(0, 2, 1, 3, 4)
            .reshape(b * c, n * f, d))


def pair_from_msa(p, x, m, hp, q):
    """Aligned cross-attention: the pair tokens of grid column block c
    attend MSA column c (keys compressed by `ratio`)."""
    b, n, _, d = x.shape
    r, c = m.shape[1], m.shape[2]
    xg = _fold_pair(layer_norm(p["norm"], x), c)
    mg = jnp.swapaxes(layer_norm(p["norm_context"], m), 1, 2).reshape(b * c, r, d)
    out = blocked(
        lambda a, k: attention(p["attn"], a, k, heads=hp["heads"],
                               ratio=hp["ratio"], q=q),
        (xg, mg), hp["cross_block"])
    f = n // c
    return (out.reshape(b, c, n, f, d).transpose(0, 2, 1, 3, 4)
            .reshape(b, n, n, d))


def msa_from_pair(p, m, x, hp, q):
    """The mirror: MSA column c attends its column block of the pair grid."""
    b, n, _, d = x.shape
    r, c = m.shape[1], m.shape[2]
    xg = _fold_pair(layer_norm(p["norm_context"], x), c)
    mg = jnp.swapaxes(layer_norm(p["norm"], m), 1, 2).reshape(b * c, r, d)
    out = blocked(
        lambda a, k: attention(p["attn"], a, k, heads=hp["heads"],
                               ratio=hp["ratio"], q=q),
        (mg, xg), hp["cross_block"])
    return jnp.swapaxes(out.reshape(b, c, r, d), 1, 2)


# the reversible layer as eight residual updates of four streams
# (x1, x2: the doubled pair stream; m1, m2: the doubled MSA stream):
# (updated stream, parameter block, function of the streams read)
REV_BLOCKS = (
    ("x1", "seq_attn", ("x2",), lambda p, hp, q, x2: pair_self(p, x2, hp, q)),
    ("x2", "seq_ff", ("x1",), lambda p, hp, q, x1: ff_block(p, x1, hp, q)),
    ("m1", "msa_attn", ("m2",), lambda p, hp, q, m2: msa_self(p, m2, hp, q)),
    ("m2", "msa_ff", ("m1",), lambda p, hp, q, m1: ff_block(p, m1, hp, q)),
    ("x1", "seq_cross", ("x2", "m2"),
     lambda p, hp, q, x2, m2: pair_from_msa(p, x2, m2, hp, q)),
    ("x2", "seq_ff2", ("x1",), lambda p, hp, q, x1: ff_block(p, x1, hp, q)),
    ("m1", "msa_cross", ("m2", "x2"),
     lambda p, hp, q, m2, x2: msa_from_pair(p, m2, x2, hp, q)),
    ("m2", "msa_ff2", ("m1",), lambda p, hp, q, m1: ff_block(p, m1, hp, q)),
)


def reversible_layer(lp, state, hp, q=None):
    state = dict(state)
    for dst, name, srcs, fn in REV_BLOCKS:
        state[dst] = state[dst] + fn(lp[name], hp, q, *[state[s] for s in srcs])
    return state


# --- front and head -----------------------------------------------------------

def front(params, seq, msa):
    """Pair grid = outer sum of token embeddings + axial positions; MSA =
    token + column position + row position embeddings."""
    n = seq.shape[1]
    e = params["token_emb"]["table"][seq]
    x = e[:, :, None, :] + e[:, None, :, :]
    pos = (params["pos_emb"]["table"][:n][:, None, :]
           + params["pos_emb_ax"]["table"][:n][None, :, :])
    rows, cols = msa.shape[1], msa.shape[2]
    m = (params["token_emb"]["table"][msa]
         + params["msa_pos_emb"]["table"][:cols][None, None]
         + params["msa_num_pos_emb"]["table"][:rows][None, :, None, :])
    return x + pos[None], m


def head(params, x, q=None):
    x = (x + jnp.swapaxes(x, 1, 2)) * 0.5
    return linear(params["head_out"], layer_norm(params["head_norm"], x), q)


def layer_at(stacked, i):
    return jax.tree_util.tree_map(lambda t: t[i], stacked)


def forward_reversible(params, seq, msa, hp, q=None):
    """Two-stream reversible forward on full masks -> distogram logits."""
    x, m = front(params, seq, msa)
    state = {"x1": x, "x2": x, "m1": m, "m2": m}
    depth = jax.tree_util.tree_leaves(params["trunk"])[0].shape[0]
    for i in range(depth):
        state = reversible_layer(layer_at(params["trunk"], i), state, hp, q)
    return head(params, (state["x1"] + state["x2"]) * 0.5, q)


# --- gradients of the reversible trunk, block by block ---------------------------
#
# Plain reverse-mode over the whole float32 trunk at a 1152^2 x 256 pair grid
# would keep every block's input (1.36 GB each). The trunk is a chain of
# residual updates s[dst] += f(s[srcs]), so walking it backwards each input
# is recovered as s[dst] -= f(s[srcs]) with the same f: one block is live at
# a time. Gradients are `jax.vjp` of the same block functions as the forward.

@functools.partial(jax.jit, static_argnames=("index", "hp_items", "q"))
def _block_fwd(p, srcs, index, hp_items, q):
    fn = REV_BLOCKS[index][3]
    return fn(p, dict(hp_items), q, *srcs)


@functools.partial(jax.jit, static_argnames=("index", "hp_items", "q"))
def _block_bwd(p, srcs, ct, index, hp_items, q):
    fn = REV_BLOCKS[index][3]
    out, vjp = jax.vjp(lambda pp, *ss: fn(pp, dict(hp_items), q, *ss), p, *srcs)
    grads = vjp(ct)
    return out, grads[0], grads[1:]


@functools.partial(jax.jit, static_argnames=("tail", "hp_items", "q"))
def _tail_grad(outer, tail_params, x1, x2, aux, tail, hp_items, q):
    return jax.value_and_grad(
        lambda o, t, a, b: tail(o, t, a, b, aux, dict(hp_items), q),
        argnums=(0, 1, 2, 3))(outer, tail_params, x1, x2)


def trunk_value_and_grad(model, seq, msa, tail, tail_params, aux, hp, q=None):
    """Loss and gradients of `tail(outer, tail_params, x1, x2, aux, hp, q)`
    through the reversible trunk. `outer` is the model's parameters outside
    the trunk (tables, head); x1, x2 the two halves of the final pair
    stream; `aux` the arrays of the example the tail reads. `tail` is a
    module-level function and `aux` an argument of the jitted call, not a
    closure: arrays closed over become constants of the program, so that
    every example would compile the tail (its eigh: a minute) anew.
    Returns (loss, gradients of the model, gradients of tail_params)."""
    hp_items = tuple(sorted(hp.items()))
    depth = jax.tree_util.tree_leaves(model["trunk"])[0].shape[0]
    outer = {k: v for k, v in model.items() if k != "trunk"}

    front_out, front_vjp = jax.vjp(lambda pp: front(pp, seq, msa), outer)
    x, m = front_out
    state = {"x1": x, "x2": x, "m1": m, "m2": m}
    del x, m, front_out  # or the streams' first values stay alive to the end
    for i in range(depth):
        lp = layer_at(model["trunk"], i)
        for k, (dst, name, srcs, _) in enumerate(REV_BLOCKS):
            state[dst] = state[dst] + _block_fwd(
                lp[name], tuple(state[s] for s in srcs), k, hp_items, q)

    loss, (d_head, d_tail, dx1, dx2) = _tail_grad(
        outer, tail_params, state["x1"], state["x2"], aux, tail, hp_items, q)
    ct = {"x1": dx1, "x2": dx2,
          "m1": jnp.zeros_like(state["m1"]), "m2": jnp.zeros_like(state["m2"])}
    del dx1, dx2

    layer_grads = []
    for i in reversed(range(depth)):
        lp = layer_at(model["trunk"], i)
        g = {}
        for k in reversed(range(len(REV_BLOCKS))):
            dst, name, srcs, _ = REV_BLOCKS[k]
            out, dp, dsrcs = _block_bwd(
                lp[name], tuple(state[s] for s in srcs), ct[dst], k, hp_items, q)
            state[dst] = state[dst] - out
            for s_, ds in zip(srcs, dsrcs):
                ct[s_] = ct[s_] + ds
            g[name] = dp
            del out, dp, dsrcs
        layer_grads.append(g)
    layer_grads.reverse()
    d_trunk = jax.tree_util.tree_map(lambda *ts: jnp.stack(ts), *layer_grads)
    (d_front,) = front_vjp((ct["x1"] + ct["x2"], ct["m1"] + ct["m2"]))
    d_outer = jax.tree_util.tree_map(jnp.add, d_head, d_front)
    return loss, {**d_outer, "trunk": d_trunk}, d_tail


# --- Adam, as the trainer's optimizer is configured ------------------------------

def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": 0}


@jax.jit
def _adam_apply(params, grads, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    def upd(p, a, b):
        return p - lr * (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + eps)
    return jax.tree_util.tree_map(upd, params, m, v), m, v


def adam_step(params, grads, opt, lr):
    t = opt["t"] + 1
    params, m, v = _adam_apply(params, grads, opt["m"], opt["v"], t, lr)
    return params, {"m": m, "v": v, "t": t}

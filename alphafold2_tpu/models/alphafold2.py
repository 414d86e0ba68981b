"""The Alphafold2 model: embeddings -> (template tower) -> dual-track trunk ->
distogram head.

Re-design of the reference model (reference alphafold2_pytorch/alphafold2.py:
328-545) as pure init/apply functions. The pair representation is the outer
sum of token embeddings plus an axial positional embedding; the MSA stream is
token + column-position + row-position embeddings (or a projection of
precomputed language-model embeddings); templates run through a pre-trunk
tower with attention along the template axis (TimeSformer-style,
reference alphafold2.py:479-524); the head symmetrizes the pair rep and
projects to distogram buckets.

Deliberate reference-bug fixes (documented divergences):
  * the `embedds` path crashes in the reference (`msa_shape` unbound,
    reference alphafold2.py:531) — here the embedds grid is a first-class
    (b, n, n, d) MSA-replacement stream;
  * templates without `templates_mask` crash in the reference (`t_mask`
    unbound, reference alphafold2.py:504) — here the mask is optional.
Reference quirks preserved for numerical parity:
  * the template tower's seq self-attention has NO residual
    (reference alphafold2.py:503);
  * `seq_pos` in a `(seq, seq_pos)` input pair is accepted and ignored (the
    reference unpacks it and never uses it, reference alphafold2.py:435-436).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from alphafold2_tpu.models.config import Alphafold2Config
from alphafold2_tpu.models.reversible import (
    reversible_trunk_apply,
    reversible_trunk_init,
)
from alphafold2_tpu.models.trunk import (
    prenorm_axial_apply,
    prenorm_axial_init,
    prenorm_ff_apply,
    prenorm_ff_init,
    sequential_trunk_apply,
    trunk_layer_init,
)
from alphafold2_tpu.ops.attention import attention_apply, attention_init
from alphafold2_tpu.ops.core import (
    embedding,
    embedding_init,
    layer_norm,
    layer_norm_init,
    linear,
    linear_init,
)
from alphafold2_tpu.telemetry.profiling import scope, scoped


def _prenorm_attn_init(key, cfg: Alphafold2Config):
    return {
        "norm": layer_norm_init(cfg.dim),
        "attn": attention_init(key, cfg.self_attn_config()),
    }


def alphafold2_init(key, cfg: Alphafold2Config):
    """Initialize all model params (embeddings, template tower, trunk, head).

    sparse_self_attn composes with reversible=True: the reversible trunk
    segments its scan by runs of equal sparse flags (models/reversible.py),
    matching the reference's `sparse_self_attn=(True, False)*6` with
    `reversible=True` capability (reference alphafold2.py:349,407-411)."""
    keys = jax.random.split(key, 16)
    params = {
        # embeddings (reference alphafold2.py:351-368)
        "token_emb": embedding_init(keys[0], cfg.num_tokens, cfg.dim),
        "pos_emb": embedding_init(keys[1], cfg.max_seq_len, cfg.dim),
        "pos_emb_ax": embedding_init(keys[2], cfg.max_seq_len, cfg.dim),
        "msa_pos_emb": embedding_init(keys[3], cfg.max_seq_len, cfg.dim),
        "msa_num_pos_emb": embedding_init(keys[4], cfg.max_num_msa, cfg.dim),
        "template_emb": embedding_init(keys[5], cfg.num_buckets, cfg.dim),
        "template_pos_emb": embedding_init(keys[6], cfg.max_seq_len, cfg.dim),
        "template_pos_emb_ax": embedding_init(keys[7], cfg.max_seq_len, cfg.dim),
        "embedd_project": linear_init(keys[8], cfg.num_embedds, cfg.dim),
        # head (reference alphafold2.py:415-418)
        "head_norm": layer_norm_init(cfg.dim),
        "head_out": linear_init(keys[9], cfg.dim, cfg.num_buckets),
    }

    # template tower (reference alphafold2.py:375-384)
    tower = []
    tkey = keys[10]
    for _ in range(cfg.template_attn_depth):
        tkey, k1, k2, k3, k4 = jax.random.split(tkey, 5)
        tower.append(
            {
                "seq_attn": prenorm_axial_init(k1, cfg, cfg.self_attn_config()),
                "template_attn": prenorm_axial_init(k2, cfg, cfg.self_attn_config()),
                "joint_attn": _prenorm_attn_init(k3, cfg),
                "template_ff": prenorm_ff_init(k4, cfg),
            }
        )
    params["template_tower"] = tower

    # trunk (reference alphafold2.py:386-405); reversible layers are stacked
    # along a leading depth axis so the trunk runs as one scanned body
    if cfg.reversible:
        params["trunk"] = reversible_trunk_init(keys[11], cfg)
    else:
        lkey = keys[11]
        layers = []
        for _ in range(cfg.depth):
            lkey, k = jax.random.split(lkey)
            layers.append(trunk_layer_init(k, cfg, reversible=False))
        params["trunk"] = layers

    return params


def _template_tower_apply(params, cfg, x, x_mask, templates, templates_mask, rng):
    """Pre-trunk template tower (reference alphafold2.py:479-524).

    x: pair rep (b, n, n, d); templates: (b, T, n, n) distogram-bucket ints.
    """
    b, num_t, n, _ = templates.shape
    d = cfg.dim
    self_cfg = cfg.self_attn_config()

    # embed templates + axial positional embedding (reference :484-493)
    t = embedding(params["template_emb"], templates, dtype=cfg.dtype)
    n_range = jnp.arange(n)
    pos = (
        embedding(params["template_pos_emb"], n_range, dtype=cfg.dtype)[:, None, :]
        + embedding(params["template_pos_emb_ax"], n_range, dtype=cfg.dtype)[None, :, :]
    )
    t = (t + pos[None, None]).reshape(b * num_t, n, n, d)

    t_mask = (
        templates_mask.reshape(b * num_t, n, n) if templates_mask is not None else None
    )
    x_mask_flat = x_mask.reshape(b, n * n) if x_mask is not None else None

    for li, layer in enumerate(params["template_tower"]):
        lrng = jax.random.fold_in(rng, li) if rng is not None else None
        rngs = jax.random.split(lrng, 4) if lrng is not None else [None] * 4

        # seq pair-rep self-attn — reference quirk: NO residual (:503)
        x = prenorm_axial_apply(layer["seq_attn"], self_cfg, x, mask=x_mask, rng=rngs[0])
        # template self-attn, with residual (:504)
        t = prenorm_axial_apply(
            layer["template_attn"], self_cfg, t, mask=t_mask, rng=rngs[1]
        ) + t

        # attention along the template axis: per pair position, the length
        # (T+1) sequence [x_pos; t_1..t_T] self-attends (:509-522)
        x_tok = x.reshape(b * n * n, 1, d)
        t_tok = t.reshape(b, num_t, n * n, d).transpose(0, 2, 1, 3).reshape(
            b * n * n, num_t, d
        )
        y = jnp.concatenate([x_tok, t_tok], axis=1)

        y_mask = None
        if templates_mask is not None and x_mask is not None:
            tm = t_mask.reshape(b, num_t, n * n).transpose(0, 2, 1).reshape(
                b * n * n, num_t
            )
            xm = x_mask_flat.reshape(b * n * n, 1)
            y_mask = jnp.concatenate([xm, tm], axis=1)

        y = attention_apply(
            layer["joint_attn"]["attn"],
            self_cfg,
            layer_norm(layer["joint_attn"]["norm"], y),
            mask=y_mask,
            rng=rngs[2],
        ) + y

        x = y[:, 0].reshape(b, n, n, d)
        t = y[:, 1:].reshape(b, n * n, num_t, d).transpose(0, 2, 1, 3).reshape(
            b * num_t, n, n, d
        )

        t = prenorm_ff_apply(layer["template_ff"], cfg, t, rng=rngs[3]) + t

    return x


def alphafold2_apply(
    params,
    cfg: Alphafold2Config,
    seq,
    msa=None,
    *,
    mask=None,
    msa_mask=None,
    templates=None,
    templates_mask=None,
    embedds=None,
    seq_pos=None,  # accepted and ignored (reference alphafold2.py:435-436)
    rng=None,
    trunk_fn=None,  # override the trunk, e.g. the sequence-parallel trunk
    # (parallel/sp_trunk.py alphafold2_apply_sp); called as
    # trunk_fn(params["trunk"], cfg, x, m, x_mask, msa_mask, rng)
):
    """Forward pass.

    Args:
      seq: (b, n) int tokens.
      msa: (b, rows, cols) int tokens, or None.
      mask: (b, n) bool.
      msa_mask: (b, rows, cols) bool.
      templates: (b, T, n, n) — int distogram buckets, or FLOAT raw
        pairwise distances in Angstroms, which are binned internally with
        the library thresholds (completes the reference's declared TODO
        "allow the main network to take care of binning raw template
        distograms", reference README.md:158; binning matches
        geometry.bucketize_distances / utils.py:29 thresholds).
      templates_mask: (b, T, n, n) bool.
      embedds: (b, n, num_embedds) precomputed language-model embeddings,
        used as the MSA-replacement stream when msa is None.
      rng: dropout key (None = deterministic / eval).

    Returns: distogram logits (b, n, n, num_buckets).
    """
    del seq_pos
    x, m, x_mask, m_mask, rng_trunk = alphafold2_front(
        params, cfg, seq, msa,
        mask=mask, msa_mask=msa_mask, templates=templates,
        templates_mask=templates_mask, embedds=embedds, rng=rng,
    )

    # trunk (reference :528-535)
    with scope("trunk"):
        if trunk_fn is not None:
            if cfg.reversible:
                # params["trunk"] is the depth-STACKED pytree when reversible
                # (reversible_trunk_init), not the layer list the hook's
                # contract documents — reject rather than hand over the wrong
                # structure
                raise ValueError(
                    "trunk_fn overrides receive the sequential layer list; "
                    "set reversible=False"
                )
            x, m = trunk_fn(params["trunk"], cfg, x, m, x_mask, m_mask, rng_trunk)
        elif cfg.reversible:
            x, m = reversible_trunk_apply(
                params["trunk"],
                cfg,
                x,
                m,
                x_mask=x_mask,
                msa_mask=m_mask,
                rng=rng_trunk,
            )
        else:
            x, m = sequential_trunk_apply(
                params["trunk"],
                cfg,
                x,
                m,
                x_mask=x_mask,
                msa_mask=m_mask,
                rng=rng_trunk,
            )

    return alphafold2_head(params, cfg, x)


def alphafold2_front(
    params,
    cfg: Alphafold2Config,
    seq,
    msa=None,
    *,
    mask=None,
    msa_mask=None,
    templates=None,
    templates_mask=None,
    embedds=None,
    rng=None,
):
    """Everything before the trunk: embeddings, MSA stream, template tower.

    Split out of `alphafold2_apply` so multi-execution drivers
    (training/segmented.py) can run front / trunk segments / head as
    separate device executions. Returns (x, m, x_mask, m_mask, rng_trunk):
    the pair grid, the MSA stream (or None), their masks, and the dropout
    key for the trunk (rng split mirrors the monolithic apply exactly).
    """
    b, n = seq.shape

    with scope("embed"):
        # pair representation: outer sum of token embeddings (reference :440-444)
        e = embedding(params["token_emb"], seq, dtype=cfg.dtype)
        x = e[:, :, None, :] + e[:, None, :, :]
        x_mask = (
            (mask[:, :, None] | mask[:, None, :]) if mask is not None else None
        )

        # axial positional embedding (reference :455-456)
        if n > cfg.max_seq_len:
            # out-of-range jnp.take fills NaN under jit (see MSA checks below)
            raise ValueError(
                f"sequence length {n} exceeds max_seq_len={cfg.max_seq_len}"
            )
        n_range = jnp.arange(n)
        pos = (
            embedding(params["pos_emb"], n_range, dtype=cfg.dtype)[:, None, :]
            + embedding(params["pos_emb_ax"], n_range, dtype=cfg.dtype)[None, :, :]
        )
        x = x + pos[None]

        # MSA stream (reference :460-472)
        m = None
        m_mask = msa_mask
        if msa is not None:
            rows, cols = msa.shape[1], msa.shape[2]
            # out-of-range jnp.take fills NaN under jit — without these checks an
            # oversized MSA silently poisons the whole forward
            if rows > cfg.max_num_msa:
                raise ValueError(
                    f"msa has {rows} rows but the row-position table holds "
                    f"max_num_msa={cfg.max_num_msa}; raise max_num_msa in the "
                    f"config (reference constants.py MAX_NUM_MSA)"
                )
            if cols > cfg.max_seq_len:
                raise ValueError(
                    f"msa has {cols} columns but the position table holds "
                    f"max_seq_len={cfg.max_seq_len}"
                )
            m = embedding(params["token_emb"], msa, dtype=cfg.dtype)
            m = m + embedding(params["msa_pos_emb"], jnp.arange(cols), dtype=cfg.dtype)[None, None]
            m = m + embedding(params["msa_num_pos_emb"], jnp.arange(rows), dtype=cfg.dtype)[None, :, None, :]
        elif embedds is not None:
            p = linear(params["embedd_project"], embedds, dtype=cfg.dtype)
            m = p[:, :, None, :] + p[:, None, :, :]  # (b, n, n, d) grid stream
            if m_mask is None:
                m_mask = x_mask  # the grid stream's validity is the pair mask

    rng_tower, rng_trunk = (
        jax.random.split(rng) if rng is not None else (None, None)
    )

    # template tower (reference :479-524)
    if templates is not None:
        if jnp.issubdtype(jnp.asarray(templates).dtype, jnp.floating):
            # raw Angstrom distances -> bucket ints (reference README.md:158
            # TODO, completed): same thresholds as the distogram head
            import numpy as np

            from alphafold2_tpu.constants import DISTANCE_THRESHOLDS

            # one source of truth: the library threshold table itself at
            # the default bucket count (searchsorted over bins[:-1] then
            # matches geometry.bucketize_distances EXACTLY, whatever the
            # table's spacing); other bucket counts resample its range so
            # labels always fit the template_emb table
            table = np.asarray(DISTANCE_THRESHOLDS, np.float32)
            if cfg.num_buckets == len(table):
                bins = table
            else:
                bins = np.linspace(table[0], table[-1], cfg.num_buckets)
            templates = jnp.searchsorted(
                jnp.asarray(bins[:-1]), jnp.asarray(templates, jnp.float32)
            ).astype(jnp.int32)
        x = scoped(
            "template_tower", _template_tower_apply,
            params, cfg, x, x_mask, templates, templates_mask, rng_tower
        )
    return x, m, x_mask, m_mask, rng_trunk


def alphafold2_head(params, cfg: Alphafold2Config, x):
    """Distogram head: symmetrize + LayerNorm + project (reference :543-545)."""
    with scope("distogram_head"):
        x = (x + jnp.swapaxes(x, 1, 2)) * 0.5
        x = layer_norm(params["head_norm"], x)
        return linear(params["head_out"], x, dtype=cfg.dtype)

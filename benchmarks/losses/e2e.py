"""The end-to-end structure loss (what `train_end2end.py` trains): trunk
-> distogram -> MDS -> 14-slot lift -> refiner -> Kabsch RMSD."""
from __future__ import annotations


def program(built: dict):
    import jax

    from alphafold2_tpu.training.e2e import e2e_loss_fn, e2e_params_init
    from alphafold2_tpu.training.harness import make_optimizer, make_train_step

    ecfg, tcfg = built["ecfg"], built["tcfg"]
    shapes = jax.eval_shape(lambda k: e2e_params_init(k, ecfg), jax.random.PRNGKey(0))
    return {
        "step": make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn),
        "param_shapes": shapes,
        "stacked": (("model", "trunk"),),
        "optimizer": make_optimizer(tcfg),
        "model_cfg": ecfg.model,
    }


def program_batch(batch: dict) -> dict:
    return {k: v[None] for k, v in batch.items()}


def reference_hp(built: dict, blocks: dict) -> dict:
    m = built["ecfg"].model
    return {"heads": m.heads, "ratio": m.cross_attn_compress_ratio,
            "tie_row": bool(m.msa_tie_row_attn),
            "mds_iters": built["ecfg"].mds_iters, **blocks}


def _reference_tail(outer, refiner, x1, x2, example, hp, q):
    from reference import af2, e2e_tail

    logits = af2.head(outer, (x1 + x2) * 0.5, q)
    return e2e_tail.structure_loss(logits, refiner, example, hp, q)


def reference_value_and_grad(params, batch, hp, q=None):
    import jax.numpy as jnp

    from reference import af2

    example = {"seq": batch["seq"], "coords": batch["coords"]}
    loss, d_model, d_refiner = af2.trunk_value_and_grad(
        params["model"], jnp.repeat(batch["seq"], 3, axis=-1), batch["msa"],
        _reference_tail, params["refiner"], example, hp, q)
    return loss, {"model": d_model, "refiner": d_refiner}

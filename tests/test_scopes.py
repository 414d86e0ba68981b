"""The names the training step carries into a device trace
(telemetry/profiling.py SCOPES): every documented name is in a compiled
toy step (the end-to-end trainer's, or the language model's), the trunk's
in the primal forward AND under the reversible backward; the lowered program is the same without them; an enabled
tracer's spans are on the profiler's clock."""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from alphafold2_tpu.models import Alphafold2Config, alphafold2_apply, alphafold2_init
from alphafold2_tpu.telemetry import NULL_TRACER, Tracer, profiling
from alphafold2_tpu.training import TrainConfig, north_star_e2e_config
from alphafold2_tpu.training.e2e import e2e_loss_fn, e2e_train_state_init
from alphafold2_tpu.training.harness import make_train_step

# inside the trunk every one of these runs forward, reconstructed and
# backward; `kv_compress` needs the toy's compress ratio of 2
IN_TRUNK = profiling.TRUNK_OP_SCOPES + profiling.TRUNK_INNER_SCOPES


def lower_toy_step():
    """The depth-1 reversible end-to-end train step at smoke shapes,
    lowered from a fresh closure (a traced function is cached with its
    names)."""
    ecfg, crop, msa_rows = north_star_e2e_config(
        depth=1, smoke=True, model_overrides={"cross_attn_compress_ratio": 2})
    tcfg = TrainConfig(grad_accum=1)
    state = jax.eval_shape(
        lambda k: e2e_train_state_init(k, ecfg, tcfg), jax.random.PRNGKey(0))
    batch = {
        "seq": jax.ShapeDtypeStruct((1, 1, crop), jnp.int32),
        "mask": jax.ShapeDtypeStruct((1, 1, crop), bool),
        "coords": jax.ShapeDtypeStruct((1, 1, crop, 14, 3), jnp.float32),
        "msa": jax.ShapeDtypeStruct((1, 1, msa_rows, crop), jnp.int32),
        "msa_mask": jax.ShapeDtypeStruct((1, 1, msa_rows, crop), bool),
    }
    step = make_train_step(ecfg, tcfg, loss_fn=e2e_loss_fn)
    return jax.jit(step).lower(state, batch, jax.random.PRNGKey(1))


def lower_template_forward():
    """A forward with templates: the one scope the e2e loss never enters."""
    cfg = Alphafold2Config(dim=16, depth=1, heads=2, dim_head=8, max_seq_len=16)
    params = jax.eval_shape(lambda k: alphafold2_init(k, cfg), jax.random.PRNGKey(0))
    return jax.jit(lambda p, s, t: alphafold2_apply(p, cfg, s, templates=t)).lower(
        params, jax.ShapeDtypeStruct((1, 8), jnp.int32),
        jax.ShapeDtypeStruct((1, 2, 8, 8), jnp.int32))


def lower_toy_lm_step(family="deepseek_v3"):
    """The decoder's train step at toy widths (`deepseek_v3`: one dense
    and one MoE layer; `zaya`: two layers; `mellum`: one period of two
    window layers and a full one): the names of models/decoder.py,
    ops/moe.py, training/lm.py."""
    from alphafold2_tpu.models.decoder import (FULL, SLIDING, DecoderConfig,
                                               MellumConfig, ZayaConfig)
    from alphafold2_tpu.training.harness import make_optimizer
    from alphafold2_tpu.training.lm import (lm_aux_update, lm_loss_fn,
                                            lm_params_init)

    cfg = DecoderConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, kv_lora_rank=16, intermediate_size=64,
        moe_intermediate_size=16, n_routed_experts=4, num_experts_per_tok=2,
        n_shared_experts=1, routed_scaling_factor=2.0, experts_held=(0, 2),
        dtype="float32")
    if family == "zaya":
        cfg = ZayaConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            moe_intermediate_size=16, num_experts=4, router_hidden_size=8,
            experts_held=(0, 2), dtype="float32")
    if family == "mellum":
        cfg = MellumConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
            layer_types=(SLIDING, SLIDING, FULL), sliding_window=6,
            rope_parameters={SLIDING: {"rope_theta": 1e4}, FULL: {"rope_theta": 1e4}},
            experts_held=(0, 2), dtype="float32")
    tcfg = TrainConfig(grad_accum=1)
    state = jax.eval_shape(
        lambda k: (lambda p: {"params": p, "opt_state": make_optimizer(tcfg).init(p),
                              "step": jnp.zeros((), jnp.int32)})(
            lm_params_init(k, cfg)), jax.random.PRNGKey(0))
    step = make_train_step(cfg, tcfg, loss_fn=lm_loss_fn,
                           aux_update=lm_aux_update(cfg))
    return jax.jit(step).lower(
        state, {"tokens": jax.ShapeDtypeStruct((1, 2, 16), jnp.int32)}, None)


def op_paths(compiled):
    """Every operation's name stack in the compiled module, wrappers of
    JAX's transformations (`jvp(...)`, `transpose(...)`) taken off."""
    paths = set()
    for name in re.findall(r'op_name="([^"]+)"', compiled.as_text()):
        elements = name.split(";")[0].split("/")[:-1]
        paths.add(tuple(re.sub(r"^(?:(?:jvp|transpose)\()+|\)+$", "", e)
                        for e in elements))
    return paths


@pytest.fixture(scope="module")
def mellum_step():
    return lower_toy_lm_step("mellum").compile()


@pytest.fixture(scope="module")
def paths(mellum_step):
    return (op_paths(lower_toy_step().compile())
            | op_paths(lower_template_forward().compile())
            | op_paths(lower_toy_lm_step().compile())
            | op_paths(lower_toy_lm_step("zaya").compile())
            | op_paths(mellum_step))


@pytest.mark.parametrize("name", profiling.SCOPES)
def test_every_documented_scope_is_in_the_compiled_step(paths, name):
    marker = profiling.REVERSIBLE_BWD_SCOPE
    forward = [p for p in paths if name in p and marker not in p]
    backward = [p for p in paths if marker in p and name in p[p.index(marker):]]
    if name == marker:
        assert backward
    elif name in IN_TRUNK:
        assert forward, f"{name} is not in the primal forward"
        assert backward, f"{name} is not under {marker}"
    else:
        assert forward


@pytest.fixture(scope="module")
def lm_op_names(mellum_step):
    """Every operation's whole name in the compiled toy decoder step."""
    return set(re.findall(r'op_name="([^"]+)"', mellum_step.as_text()))


# the expert layer's backward is a loop of its own (ops/moe.py _walk_bwd):
# under the layer's transpose, the caller's `moe`, then the loop's body
_BACKWARD_LOOP = r"transpose\(jvp\(decoder_layers\)\)/.*/moe/while/body/"


@pytest.mark.parametrize("inner", ["dispatch", "experts", "combine"])
def test_expert_backward_loop_carries_the_layers_names(lm_op_names, inner):
    in_loop = re.compile(_BACKWARD_LOOP + rf"(?:.*/)?{inner}/")
    assert any(in_loop.search(name) for name in lm_op_names)


def test_expert_backward_loop_recomputes_its_block_as_remat(lm_op_names):
    again = re.compile(_BACKWARD_LOOP + r".*rematted_computation/experts/")
    assert any(again.search(name) for name in lm_op_names)


def test_checkpointed_expert_layer_is_one_loop_a_direction_and_no_conditional():
    """A layer-like `jax.checkpoint` around `moe_apply`: the compiled
    gradient holds the expert loop twice (forward, backward) and not a
    third time for the checkpoint's second forward, whose result nothing
    reads; a loop over live blocks has no branch for a skipped one."""
    from alphafold2_tpu.ops import moe

    n, d, f = 64, 32, 16
    shapes = {"gate": (2, d, f), "up": (2, d, f), "down": (2, f, d)}
    params = {
        "router": {"w": jax.ShapeDtypeStruct((d, 4), jnp.float32)},
        "experts": {k: {"w": jax.ShapeDtypeStruct(v, jnp.float32)}
                    for k, v in shapes.items()}}

    def layer(p, h):
        x = jnp.tanh(h)
        routing = moe.route_softmax(moe.router_logits(p, x), None, 2, norm_topk=True)
        return h + moe.moe_apply(p, x, routing, held=(1, 3))[0]

    compiled = jax.jit(jax.grad(
        lambda p, h: jnp.sum(jnp.sin(jax.checkpoint(layer)(p, h))),
        argnums=(0, 1))).lower(
            params, jax.ShapeDtypeStruct((n, d), jnp.float32)).compile().as_text()
    assert len(re.findall(r" while\(", compiled)) == 2
    assert not re.search(r" conditional\(", compiled)


def test_scopes_are_two_levels_and_undocumented_names_are_refused():
    assert len(set(profiling.SCOPES)) == len(profiling.SCOPES)
    assert set(profiling.OUTER_SCOPES).isdisjoint(profiling.INNER_SCOPES)
    with pytest.raises(ValueError, match="not a documented scope"):
        profiling.scope("attn_softmax")


def test_the_program_is_the_same_without_the_names(monkeypatch):
    named = lower_toy_step()
    monkeypatch.setattr(profiling, "_named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower_toy_step()
    # the names are debug locations and nothing else: with locations left
    # out of the text (the default) the two modules are the same text
    assert named.as_text() == bare.as_text()
    with_names = named.as_text(debug_info=True)
    without = bare.as_text(debug_info=True)
    for name in ("attn_core", "geglu", "reversible_bwd", "optimizer",
                 "kabsch_loss"):
        element = re.compile(rf'[("/]{name}[)/]')  # `jvp(kabsch_loss)/mul`
        assert element.search(with_names)
        assert not element.search(without)


SPANS = ("train.step", "serving.execute", "fleet.enqueue", "featurize.run")


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """Host events of one profiler capture taken while an enabled tracer
    and the disabled singleton ran, and the tracer."""
    out = str(tmp_path_factory.mktemp("capture"))
    tracer = Tracer()
    jax.profiler.start_trace(out)
    try:
        for name in SPANS:
            with tracer.span(name, cat="test"):
                with NULL_TRACER.span("null.span"):
                    jnp.ones(8).block_until_ready()
        tracer.add("serving.queue_wait", 0.01)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(out + "/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = [e.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]
    return host, tracer


@pytest.mark.parametrize("name", SPANS)
def test_enabled_tracer_span_is_a_host_event_of_a_capture(captured, name):
    host, tracer = captured
    assert host.count(name) == 1
    assert tracer.summary()[name]["count"] == 1


def test_null_tracer_stays_the_shared_singleton(captured):
    host, tracer = captured
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b", cat="x", k=1)
    assert "null.span" not in host
    # a span recorded after the fact is in the tracer's own export only
    assert "serving.queue_wait" in tracer.summary()
    assert "serving.queue_wait" not in host

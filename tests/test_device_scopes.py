"""Device scopes (PR 34): every matrix product of every served program is
traced under exactly one registered ``cdt.<layer>`` scope.

The walk is over the traced jaxpr, where the compiler's ``op_name`` comes
from: an equation's name stack, prefixed by the stacks of the equations
that hold its sub-jaxpr (a scan, a ``shard_map``, a ``cond``), is what the
TPU trace later shows as the operation's ``tf_op`` and what
``cdtbench/device_layers.py`` resolves to a layer.
"""

import re
import types

import pytest

import jax
import jax.numpy as jnp

from comfyui_distributed_tpu.parallel import build_mesh
from comfyui_distributed_tpu.telemetry import device_scopes
from comfyui_distributed_tpu.telemetry.device_scopes import (DEVICE_LAYERS,
                                                             device_scope)
from comfyui_distributed_tpu.utils import flops

LAYER = re.compile(r"cdt\.([A-Za-z0-9_]+)")
COUNTED = ("dot_general", "conv_general_dilated", "pallas_call")
NAMES = [name for name, _ in DEVICE_LAYERS]


# --- the registry -------------------------------------------------------------


def test_the_registry_is_small_unique_and_documented():
    assert len(NAMES) == len(set(NAMES)) <= device_scopes.MAX_DEVICE_LAYERS
    for name, what in DEVICE_LAYERS:
        assert re.fullmatch(r"[a-z][a-z0-9_]*", name), name
        assert len(what) > 20 and "\n" not in what, name
    image = {"resnet", "attn_proj", "attn_core", "ffn", "norm_mod",
             "sampler", "vae_decode"}
    language = {"llm_attn", "llm_router", "llm_experts", "llm_shared_ffn",
                "llm_mix", "llm_norm", "llm_head", "llm_sample"}
    assert image | language <= set(NAMES)


def test_every_layer_is_named_in_the_documents():
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    for doc in ("docs/telemetry.md", "PERF.md"):
        text = (root / doc).read_text()
        missing = [n for n in NAMES if f"`{n}`" not in text]
        assert not missing, f"{doc} does not name {missing}"


def test_an_unregistered_layer_is_refused_at_trace_time():
    with pytest.raises(ValueError, match="not registered"):
        device_scope("nope")
    with pytest.raises(ValueError, match="not registered"):
        device_scopes.device_scoped("nope")
    with pytest.raises(ValueError, match="not registered"):
        jax.make_jaxpr(lambda x: _scoped_double(x, "cdt.resnet"))(1.0)


def _scoped_double(x, layer):
    with device_scope(layer):
        return x * 2


def test_a_scope_is_metadata_only():
    def plain(x):
        return jnp.tanh(x @ x) + 1.0

    def scoped(x):
        with device_scope("ffn"):
            return jnp.tanh(x @ x) + 1.0

    x = jnp.ones((4, 4))
    a, b = jax.make_jaxpr(plain)(x), jax.make_jaxpr(scoped)(x)
    assert str(a) == str(b)                     # the same equations
    stacks = [str(e.source_info.name_stack) for e in b.jaxpr.eqns]
    assert stacks and all(s == "cdt.ffn" for s in stacks)
    assert (plain(x) == scoped(x)).all()


# --- the walk -----------------------------------------------------------------


def _sub_jaxprs(params):
    for value in params.values():
        for item in (value if isinstance(value, (tuple, list)) else (value,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        return flops._dot_flops(eqn)
    if name == "conv_general_dilated":
        return flops._conv_flops(eqn)
    cost = eqn.params.get("cost_estimate")
    return float(cost.flops) if cost is not None else 0.0


def walk(jaxpr, prefix="", times=1.0):
    """``(primitive, whole name stack, operations)`` of every counted
    equation, a scan's body as often as it runs."""
    for eqn in jaxpr.eqns:
        stack = f"{prefix}/{eqn.source_info.name_stack}"
        name = eqn.primitive.name
        if name in COUNTED:
            yield name, stack, times * _eqn_flops(eqn)
            continue
        inner_times = times * (eqn.params["length"] if name == "scan" else 1)
        for sub in _sub_jaxprs(eqn.params):
            yield from walk(sub, stack, inner_times)


# --- the served programs, at the tiny presets ---------------------------------


def _mesh():
    return build_mesh({"dp": 1}, devices=jax.devices()[:1])


def _unet_lane():
    from comfyui_distributed_tpu.diffusion.pipeline import (GenerationSpec,
                                                            Txt2ImgPipeline)
    from comfyui_distributed_tpu.models.unet import UNetConfig, init_unet
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig

    cfg = UNetConfig.tiny()
    model, params = init_unet(cfg, jax.random.key(0),
                              sample_shape=(8, 8, 4), context_len=16)
    vae = AutoencoderKL(VAEConfig.tiny()).init(jax.random.key(1),
                                               image_hw=(16, 16))
    pipe = Txt2ImgPipeline(model, params, vae)
    ctx = jnp.full((1, 16, cfg.context_dim), 0.1)
    spec = GenerationSpec(height=16, width=16, steps=4, guidance_scale=2.0)
    fns = pipe.preemptible_fns(_mesh(), spec)
    y = jnp.zeros((1, cfg.adm_in_channels), jnp.float32)
    args = (jax.random.key(0), ctx, ctx * 0.5, y, y)
    return fns, args


def _txt2img_seg():
    fns, args = _unet_lane()
    carry = jax.eval_shape(fns["prep"].jitted, fns["prep"].weights, *args)
    seg = fns["seg"](2)
    return seg.jitted, (seg.weights, *args, jnp.int32(0), carry)


def _fin():
    fns, args = _unet_lane()
    carry = jax.eval_shape(fns["prep"].jitted, fns["prep"].weights, *args)
    return fns["fin"].jitted, (fns["fin"].weights, carry)


def _flow_seg():
    from comfyui_distributed_tpu.diffusion.pipeline_flow import (FlowPipeline,
                                                                 FlowSpec)
    from comfyui_distributed_tpu.models.dit import DiTConfig, init_dit
    from comfyui_distributed_tpu.models.vae import AutoencoderKL, VAEConfig

    cfg = DiTConfig.sd3_tiny()
    model, params = init_dit(cfg, jax.random.key(0), sample_hw=(8, 8),
                             context_len=6)
    vae = AutoencoderKL(VAEConfig.tiny()).init(jax.random.key(1),
                                               image_hw=(16, 16))
    pipe = FlowPipeline(model, params, vae)
    ctx = jnp.full((1, 6, cfg.context_dim), 0.1)
    pooled = jnp.full((1, cfg.pooled_dim), 0.1)
    spec = FlowSpec(height=16, width=16, steps=4, cfg=5.0)
    fns = pipe.segment_fns(_mesh(), spec)
    args = (jax.random.key(0), ctx, pooled, ctx * 0.5, pooled * 0.5)
    carry = jax.eval_shape(fns["prep"].jitted, fns["prep"].weights, *args)
    cast = (jax.eval_shape(fns["cast"].jitted, fns["cast"].weights)
            if fns["cast"] else ())
    seg = fns["seg"](2)
    return seg.jitted, (seg.weights, *args, jnp.int32(0), carry, cast)


def _llm(module: str, config: str, tokens: int):
    import importlib

    from comfyui_distributed_tpu.diffusion import pipeline_llm

    mod = importlib.import_module(
        f"comfyui_distributed_tpu.models.{module}")
    cfg = getattr(mod, config).tiny()
    params = cfg.model.init(cfg, jax.random.key(0), abstract=True)
    pipe = pipeline_llm.LLMPipeline(cfg, params)
    prefill, decode = pipe.programs(tokens, 8)
    ids = jax.ShapeDtypeStruct((tokens,), jnp.int32)
    return types.SimpleNamespace(pipe=pipe, prefill=prefill, decode=decode,
                                 ids=ids)


def _llm_prefill(module, config, tokens):
    m = _llm(module, config, tokens)
    return m.prefill.jitted, (m.pipe.params, m.ids)


def _llm_decode(module, config, tokens):
    m = _llm(module, config, tokens)
    logits, cache, *_ = jax.eval_shape(m.prefill.jitted, m.pipe.params,
                                       m.ids)
    return m.decode.jitted, (m.pipe.params, logits, cache,
                             jax.random.key(0), jnp.float32(0.7))


LLMS = {"ling-tiny": ("llm_hybrid", "LLMConfig", 24),
        "motif-tiny": ("llm_motif", "MotifConfig", 24),
        "kimi-tiny": ("llm_kimi", "KimiConfig", 37),     # three chunks of 16
        "jamba-tiny": ("llm_jamba", "JambaConfig", 37),
        "trinity-tiny": ("llm_trinity", "TrinityConfig", 21),  # 2 chunks + 5
        "longcat-tiny": ("llm_longcat", "LongcatConfig", 37),  # three chunks
        "sala-tiny": ("llm_sala", "SalaConfig", 40),   # past its dense_len
        "glm-tiny": ("llm_glm", "GlmConfig", 40),      # past its index_topk
        "keye-tiny": ("llm_keye", "KeyeConfig", 40),   # past its topk
        "zaya-tiny": ("llm_zaya", "ZayaConfig", 37),   # a padded last chunk
        "brumby-tiny": ("llm_brumby", "BrumbyConfig", 37),   # and under a gate
        "mimo-tiny": ("llm_mimo", "MimoConfig", 37)}   # 2 chunks + 5: a ring

PROGRAMS = {"txt2img_seg": _txt2img_seg, "flow_seg": _flow_seg, "fin": _fin}
for _name, _how in LLMS.items():
    PROGRAMS[f"llm_prefill:{_name}"] = (
        lambda how=_how: _llm_prefill(*how))
    PROGRAMS[f"llm_decode:{_name}"] = (
        lambda how=_how: _llm_decode(*how))

# what a program must open, so that a scope that silently stopped being
# reached is seen here and not on the chip
EXPECTED = {
    "txt2img_seg": {"resnet", "attn_proj", "attn_core", "ffn", "norm_mod"},
    "flow_seg": {"attn_proj", "attn_core", "ffn", "norm_mod"},
    "fin": {"vae_decode"},
    "llm_prefill:ling-tiny": {"llm_attn", "llm_router", "llm_experts",
                              "llm_shared_ffn", "llm_head"},
    "llm_decode:ling-tiny": {"llm_attn", "llm_router", "llm_experts",
                             "llm_shared_ffn", "llm_head"},
    "llm_prefill:motif-tiny": {"llm_attn", "llm_router", "llm_experts",
                               "llm_shared_ffn", "llm_head", "llm_mix"},
    "llm_decode:motif-tiny": {"llm_attn", "llm_router", "llm_experts",
                              "llm_shared_ffn", "llm_head", "llm_mix"},
    "llm_prefill:kimi-tiny": {"llm_attn", "llm_router", "llm_experts",
                              "llm_shared_ffn", "llm_head"},
    "llm_decode:kimi-tiny": {"llm_attn", "llm_router", "llm_experts",
                             "llm_shared_ffn", "llm_head"},
    # no expert layer: no router, no experts; the mixers are llm_ssm
    "llm_prefill:jamba-tiny": {"llm_ssm", "llm_attn", "llm_shared_ffn",
                               "llm_head"},
    "llm_decode:jamba-tiny": {"llm_ssm", "llm_attn", "llm_shared_ffn",
                              "llm_head"},
    # four norms a block: the two after the sublayers are llm_norm's too
    "llm_prefill:trinity-tiny": {"llm_attn", "llm_router", "llm_experts",
                                 "llm_shared_ffn", "llm_head"},
    "llm_decode:trinity-tiny": {"llm_attn", "llm_router", "llm_experts",
                                "llm_shared_ffn", "llm_head"},
    # no shared expert: llm_shared_ffn is the two dense FFNs of a double
    # layer; the identity experts' mix and the branch's join are llm_experts'
    "llm_prefill:longcat-tiny": {"llm_attn", "llm_router", "llm_experts",
                                 "llm_shared_ffn", "llm_head"},
    "llm_decode:longcat-tiny": {"llm_attn", "llm_router", "llm_experts",
                                "llm_shared_ffn", "llm_head"},
    # no expert layer; selection, sparse core and the linear recurrence are
    # plain named scopes BELOW cdt.llm_attn (there is no seventeenth layer)
    "llm_prefill:sala-tiny": {"llm_attn", "llm_shared_ffn", "llm_head"},
    "llm_decode:sala-tiny": {"llm_attn", "llm_shared_ffn", "llm_head"},
    # the indexer's scores, the selection and the attention under it are
    # plain named scopes BELOW cdt.llm_attn too (sixteen layers there are)
    "llm_prefill:glm-tiny": {"llm_attn", "llm_router", "llm_experts",
                             "llm_shared_ffn", "llm_head"},
    "llm_decode:glm-tiny": {"llm_attn", "llm_router", "llm_experts",
                            "llm_shared_ffn", "llm_head"},
    # no shared expert and no dense layer: nothing opens llm_shared_ffn
    "llm_prefill:keye-tiny": {"llm_attn", "llm_router", "llm_experts",
                              "llm_head"},
    "llm_decode:keye-tiny": {"llm_attn", "llm_router", "llm_experts",
                             "llm_head"},
    # the router is an MLP: its products are llm_router's; no shared expert
    "llm_prefill:zaya-tiny": {"llm_attn", "llm_router", "llm_experts",
                              "llm_head"},
    "llm_decode:zaya-tiny": {"llm_attn", "llm_router", "llm_experts",
                             "llm_head"},
    # no expert layer and no K/V row: retention is a plain named scope BELOW
    # cdt.llm_attn (the sixteen stay sixteen)
    "llm_prefill:brumby-tiny": {"llm_attn", "llm_shared_ffn", "llm_head"},
    "llm_decode:brumby-tiny": {"llm_attn", "llm_shared_ffn", "llm_head"},
    # no shared expert: llm_shared_ffn is the one dense layer; the two cores
    # are plain named scopes BELOW cdt.llm_attn (the sixteen stay sixteen)
    "llm_prefill:mimo-tiny": {"llm_attn", "llm_router", "llm_experts",
                              "llm_shared_ffn", "llm_head"},
    "llm_decode:mimo-tiny": {"llm_attn", "llm_router", "llm_experts",
                             "llm_shared_ffn", "llm_head"},
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_product_of_a_served_program_is_under_exactly_one_layer(
        program):
    fn, args = PROGRAMS[program]()
    closed = jax.make_jaxpr(fn)(*args)
    seen = list(walk(closed.jaxpr))
    assert seen, "the walk found no matrix product: it is looking wrong"
    layers, named, total = set(), 0.0, 0.0
    for primitive, stack, ops in seen:
        found = LAYER.findall(stack)
        assert len(found) == 1 and found[0] in NAMES, (
            f"{primitive} under {stack!r}: expected exactly one registered "
            f"cdt.<layer>, found {found}")
        layers.add(found[0])
        named += ops
        total += ops
    assert total > 0 and named / total >= 0.95
    assert EXPECTED[program] <= layers, (
        f"{program} opened {sorted(layers)}, not "
        f"{sorted(EXPECTED[program] - layers)}")


def test_the_selecting_rewriters_work_is_named_below_its_layer():
    """``select``, ``sparse_core`` and ``lightning`` are plain named scopes
    under ``cdt.llm_attn`` (what ``cdtbench/kinds/sala.py: scope_seconds``
    reads from a trace): every product of the three is under exactly one of
    them, and under the one registered layer."""
    plain = re.compile(r"/(select|sparse_core|lightning)(?:/|$)")
    for program in ("llm_prefill:sala-tiny", "llm_decode:sala-tiny"):
        fn, args = PROGRAMS[program]()
        seen = list(walk(jax.make_jaxpr(fn)(*args).jaxpr))
        below = {}
        for primitive, stack, ops in seen:
            found = plain.findall(stack)
            if found:
                assert len(found) == 1 and LAYER.findall(stack) \
                    == ["llm_attn"], stack
                below[found[0]] = below.get(found[0], 0) + ops
        want = {"select", "sparse_core", "lightning"} \
            if program.startswith("llm_prefill") else {"select", "lightning"}
        assert set(below) >= want and all(below.values()), below


def test_the_index_selecting_rewriters_work_is_named_below_its_layer():
    """``llm_index`` and ``llm_sparse_attn`` are plain named scopes under
    ``cdt.llm_attn`` (what ``cdtbench/kinds/glm.py: scope_seconds`` reads
    from a trace): every product of the scores and of the attention over
    the kept keys is under exactly one of them, and under the one
    registered layer (the selection multiplies nothing)."""
    plain = re.compile(r"/(llm_index|llm_select|llm_sparse_attn)(?:/|$)")
    for program in ("llm_prefill:glm-tiny", "llm_decode:glm-tiny",
                    "llm_prefill:keye-tiny", "llm_decode:keye-tiny"):
        fn, args = PROGRAMS[program]()
        seen = list(walk(jax.make_jaxpr(fn)(*args).jaxpr))
        below = {}
        for primitive, stack, ops in seen:
            found = plain.findall(stack)
            if found:
                assert len(found) == 1 and LAYER.findall(stack) \
                    == ["llm_attn"], stack
                below[found[0]] = below.get(found[0], 0) + ops
        assert set(below) == {"llm_index", "llm_sparse_attn"} \
            and all(below.values()), below


def test_the_latent_rewriters_work_is_named_below_its_layer():
    """``llm_cca_mix`` (the grouped convolution's mixes: the only products
    between the latent projections and the core) and ``llm_cca_core`` are
    plain named scopes under ``cdt.llm_attn``: every product of the two is
    under exactly one of them and under the one registered layer; the latent
    and output projections are under the layer alone; the router's MLP is
    ``cdt.llm_router``'s."""
    plain = re.compile(r"/(llm_cca_mix|llm_cca_core)(?:/|$)")
    for program in ("llm_prefill:zaya-tiny", "llm_decode:zaya-tiny"):
        fn, args = PROGRAMS[program]()
        seen = list(walk(jax.make_jaxpr(fn)(*args).jaxpr))
        below, layers = {}, {}
        for primitive, stack, ops in seen:
            (layer,) = LAYER.findall(stack)
            layers[layer] = layers.get(layer, 0) + 1
            found = plain.findall(stack)
            if found:
                assert len(found) == 1 and layer == "llm_attn", stack
                below[found[0]] = below.get(found[0], 0) + ops
        assert set(below) == {"llm_cca_mix", "llm_cca_core"} \
            and all(below.values()), below
        # a layer's router: the down-projection, two hidden layers, the output
        assert layers["llm_router"] >= 4 * 3


def test_the_retention_rewriters_work_is_named_below_its_layer():
    """``llm_retention`` (the gate's product, the products inside a block,
    the state's read and update) is a plain named scope under
    ``cdt.llm_attn`` (what ``cdtbench/kinds/brumby.py: retention_seconds``
    reads from a trace): its products are under the one registered layer;
    the q/k/v and output projections are under the layer alone."""
    plain = re.compile(r"/(llm_retention)(?:/|$)")
    for program in ("llm_prefill:brumby-tiny", "llm_decode:brumby-tiny"):
        fn, args = PROGRAMS[program]()
        seen = list(walk(jax.make_jaxpr(fn)(*args).jaxpr))
        below = above = 0.0
        for primitive, stack, ops in seen:
            (layer,) = LAYER.findall(stack)
            if plain.findall(stack):
                assert layer == "llm_attn", stack
                below += ops
            elif layer == "llm_attn":
                above += ops
        assert below > 0 and above > 0, (program, below, above)


def test_the_window_and_sink_rewriters_cores_are_named_below_their_layer():
    """``llm_swa_core`` (a window layer's band with its sink, the ring's
    step) and ``llm_full_core`` (a full layer's causal core, the buffer's
    step) are plain named scopes under ``cdt.llm_attn`` (what
    ``cdtbench/kinds/mimo.py: scope_seconds`` reads from a trace): every
    product of a core is under exactly one of them and under the one
    registered layer; the q/k/v and output projections are under the layer
    alone. Five window layers to two full: both carry products."""
    plain = re.compile(r"/(llm_swa_core|llm_full_core)(?:/|$)")
    for program in ("llm_prefill:mimo-tiny", "llm_decode:mimo-tiny"):
        fn, args = PROGRAMS[program]()
        seen = list(walk(jax.make_jaxpr(fn)(*args).jaxpr))
        below, above = {}, 0.0
        for primitive, stack, ops in seen:
            (layer,) = LAYER.findall(stack)
            found = plain.findall(stack)
            if found:
                assert len(found) == 1 and layer == "llm_attn", stack
                below[found[0]] = below.get(found[0], 0.0) + ops
            elif layer == "llm_attn":
                above += ops
        assert set(below) == {"llm_swa_core", "llm_full_core"} \
            and all(below.values()) and above > 0, (program, below, above)

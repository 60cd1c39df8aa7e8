"""openPangu-Ultra-MoE decoder (`model_type: pangu_ultra_moe`): latent
attention (MLA: a low-rank query, and ONE cached row a position — the
normalised 512-wide latent and a 64-wide rotary key shared by all heads),
an RMSNorm before AND after each sublayer ("sandwich"), `first_k_dense_replace`
leading layers with a dense SwiGLU and expert layers after them (sigmoid
top-k router, gates normalised over the k and scaled by
`routed_scaling_factor`, one shared expert), and a head untied from the
embedding.

The Layer holds the parameters; the mathematics is `latent_block.py`'s,
which the serving engine's programs call too. The expert layers may be
one chip's share of an expert-parallel deployment: `num_local_experts`
held from `expert_rank * num_local_experts` on, the router over all
`n_routed_experts` (`incubate/nn/functional/dropless_moe.py`).

Serving: `ServingEngine(model)` (paged latent cache, one pool a layer).
Not served here, each refused by name: int8/int4 pools, weight-only
quantization, speculative decoding (the config's multi-token-prediction
module, its drafter, is not built), the prefix cache, and
`text/generation.py`'s static engine.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ... import nn
from ...core.dispatch import op_call
from ...nn.initializer import Constant, Normal
from . import latent_block as lb


@dataclass
class PanguUltraMoEConfig:
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432      # the dense layers' FFN
    moe_intermediate_size: int = 2048   # the width of ONE expert
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    sandwich_norm: bool = True
    #: the experts held here: `num_local_experts` from
    #: `expert_rank * num_local_experts` on (None: all of them)
    num_local_experts: int | None = None
    expert_rank: int = 0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_local_experts is None:
            self.num_local_experts = self.n_routed_experts
        if self.first_expert + self.num_local_experts > self.n_routed_experts:
            raise ValueError(
                f"rank {self.expert_rank} x {self.num_local_experts} held "
                f"experts passes n_routed_experts {self.n_routed_experts}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} of "
                f"{self.num_hidden_layers} layers")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotate-half)")
        for key, want in (("tie_word_embeddings", False),
                          ("norm_topk_prob", True), ("sandwich_norm", True)):
            if getattr(self, key) is not want:
                raise ValueError(f"pangu_ultra_moe is written for {key}="
                                 f"{want}")

    @property
    def first_expert(self) -> int:
        return self.expert_rank * self.num_local_experts

    @property
    def layer_types(self) -> tuple:
        return tuple(lb.DENSE if i < self.first_k_dense_replace
                     else lb.EXPERTS for i in range(self.num_hidden_layers))

    def block_spec(self) -> lb.BlockSpec:
        return lb.BlockSpec(
            hidden_size=self.hidden_size,
            num_heads=self.num_attention_heads,
            qk_nope_dim=self.qk_nope_head_dim,
            qk_rope_dim=self.qk_rope_head_dim, v_dim=self.v_head_dim,
            q_rank=self.q_lora_rank, kv_rank=self.kv_lora_rank,
            eps=self.rms_norm_eps, rope_theta=float(self.rope_theta),
            layer_types=self.layer_types,
            num_experts=self.n_routed_experts,
            top_k=self.num_experts_per_tok,
            first_expert=self.first_expert,
            num_local_experts=self.num_local_experts,
            num_shared_experts=self.n_shared_experts,
            routed_scale=float(self.routed_scaling_factor))


def pangu_ultra_moe_tiny_config(**kw) -> PanguUltraMoEConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16,
                max_position_embeddings=256, n_routed_experts=16,
                num_experts_per_tok=4)
    base.update(kw)
    return PanguUltraMoEConfig(**base)


class _Weights(nn.Layer):
    """A bag of parameters: {name: shape}, all normal(0, std) — or ones
    for the names in `ones` (norm gains), zeros for those in `zeros`
    (zero-centred gains, biases)."""

    def __init__(self, shapes: dict, config, ones=(), zeros=()):
        super().__init__()
        for name, shape in shapes.items():
            init = Constant(1.0) if name in ones \
                else Constant(0.0) if name in zeros \
                else Normal(0.0, config.initializer_range)
            setattr(self, name, self.create_parameter(
                list(shape), dtype=config.dtype, default_initializer=init))


def _gain(c, width, zero_centred=False):
    if zero_centred:
        return _Weights({"weight": (width,)}, c, zeros=("weight",))
    return _Weights({"weight": (width,)}, c, ones=("weight",))


def _attention(c, zero_centred=False, gated=False) -> _Weights:
    """The latent attention's parameters, matrices [in, out]; `kv_b_proj`
    holds each head's [Wuk | Wuv] columns side by side, as published;
    `gate_proj` (`gated`) the output gate's [H, heads x dv]."""
    h, nh = c.hidden_size, c.num_attention_heads
    dq = c.qk_nope_head_dim + c.qk_rope_head_dim
    gains = ("q_a_layernorm", "kv_a_layernorm")
    extra = {"gate_proj": (h, nh * c.v_head_dim)} if gated else {}
    return _Weights({
        "q_a_proj": (h, c.q_lora_rank),
        "q_a_layernorm": (c.q_lora_rank,),
        "q_b_proj": (c.q_lora_rank, nh * dq),
        "kv_a_proj_with_mqa": (h, c.kv_lora_rank + c.qk_rope_head_dim),
        "kv_a_layernorm": (c.kv_lora_rank,),
        "kv_b_proj": (c.kv_lora_rank,
                      nh * (c.qk_nope_head_dim + c.v_head_dim)),
        "o_proj": (nh * c.v_head_dim, h), **extra}, c,
        **{"zeros" if zero_centred else "ones": gains})


class PanguUltraMoESparseMoe(nn.Layer):
    """The expert layer's parameters: the router over all experts, the
    held routed experts stacked, the shared expert(s) side by side."""

    def __init__(self, c: PanguUltraMoEConfig):
        super().__init__()
        h, f = c.hidden_size, c.moe_intermediate_size
        n, s = c.num_local_experts, c.n_shared_experts
        self.router = _Weights({"weight": (h, c.n_routed_experts)}, c)
        self.experts = _Weights({"gate_proj": (n, h, f),
                                 "up_proj": (n, h, f),
                                 "down_proj": (n, f, h)}, c)
        self.shared_experts = _Weights({"gate_proj": (h, s * f),
                                        "up_proj": (h, s * f),
                                        "down_proj": (s * f, h)}, c)


class PanguUltraMoEDecoderLayer(nn.Layer):
    def __init__(self, c: PanguUltraMoEConfig, kind: str):
        super().__init__()
        h = c.hidden_size
        self.kind = kind
        self.input_layernorm = _gain(c, h)
        self.post_attention_layernorm = _gain(c, h)
        self.pre_mlp_layernorm = _gain(c, h)
        self.post_mlp_layernorm = _gain(c, h)
        self.self_attn = _attention(c)
        if kind == lb.DENSE:
            f = c.intermediate_size
            self.mlp = _Weights({"gate_proj": (h, f), "up_proj": (h, f),
                                 "down_proj": (f, h)}, c)
        else:
            self.mlp = PanguUltraMoESparseMoe(c)

    def arrays(self, get) -> dict:
        """`get(parameter)` for each of the layer's parameters, under
        `latent_block.block`'s names."""
        a, m = self.self_attn, self.mlp
        out = {"ln_in": get(self.input_layernorm.weight),
               "ln_post_attn": get(self.post_attention_layernorm.weight),
               "ln_pre_ffn": get(self.pre_mlp_layernorm.weight),
               "ln_post_ffn": get(self.post_mlp_layernorm.weight),
               "q_a": get(a.q_a_proj), "q_a_ln": get(a.q_a_layernorm),
               "q_b": get(a.q_b_proj),
               "kv_a": get(a.kv_a_proj_with_mqa),
               "kv_a_ln": get(a.kv_a_layernorm),
               "kv_b": get(a.kv_b_proj), "o": get(a.o_proj)}
        if self.kind == lb.DENSE:
            out.update(gate=get(m.gate_proj), up=get(m.up_proj),
                       down=get(m.down_proj))
        else:
            out.update(router=get(m.router.weight),
                       experts_gate=get(m.experts.gate_proj),
                       experts_up=get(m.experts.up_proj),
                       experts_down=get(m.experts.down_proj),
                       shared_gate=get(m.shared_experts.gate_proj),
                       shared_up=get(m.shared_experts.up_proj),
                       shared_down=get(m.shared_experts.down_proj))
        return out


class PanguUltraMoEModel(nn.Layer):
    def __init__(self, c: PanguUltraMoEConfig):
        super().__init__()
        self.embed_tokens = _Weights(
            {"weight": (c.vocab_size, c.hidden_size)}, c)
        self.layers = nn.LayerList(
            [PanguUltraMoEDecoderLayer(c, kind) for kind in c.layer_types])
        self.norm = _gain(c, c.hidden_size)


@functools.partial(jax.jit, static_argnums=0)
def _forward(spec, params, ids):
    return jax.vmap(lambda row: lb.forward_sequence(params, row, spec))(ids)


class PanguUltraMoEForCausalLM(nn.Layer):
    _gen_arch = "pangu_ultra_moe"  # serving-engine layout (inference/layered.py)

    def __init__(self, config: PanguUltraMoEConfig):
        super().__init__()
        self.config = config
        self.model = PanguUltraMoEModel(config)
        self.lm_head = _Weights(
            {"weight": (config.vocab_size, config.hidden_size)}, config)

    def serving_arrays(self, get=lambda p: p._data) -> dict:
        """{"embed", "final_ln", "head", "layers": [...]} under the names
        the step programs use; by default the parameters' own buffers, by
        reference (no copy is made)."""
        m = self.model
        return {"embed": get(m.embed_tokens.weight),
                "final_ln": get(m.norm.weight),
                "head": get(self.lm_head.weight),
                "layers": [layer.arrays(get) for layer in m.layers]}

    def forward(self, input_ids, labels=None):
        """Logits [B, S, V] float32 (with `labels`: the mean token
        cross-entropy, position t scored against label t)."""
        spec = self.config.block_spec()
        tensors = self.parameters()
        at = {id(p): i for i, p in enumerate(tensors)}
        where = self.serving_arrays(lambda p: at[id(p)])

        def fn(ids, *arrays):
            params = jax.tree_util.tree_map(lambda i: arrays[i], where)
            return _forward(spec, params, ids)

        ids = input_ids._data if hasattr(input_ids, "_data") \
            else jnp.asarray(np.asarray(input_ids))
        out = op_call(fn, ids.astype(jnp.int32), *tensors,
                      name="pangu_ultra_moe_forward")
        if labels is None:
            return out
        from ...nn import functional as F

        return F.cross_entropy(out.reshape([-1, self.config.vocab_size]),
                               labels.reshape([-1]))

    def generate(self, input_ids, max_new_tokens=32, engine="paged", **kw):
        """Greedy or sampled continuation through the paged serving
        engine; the static single-program engine does not know this
        architecture."""
        if engine != "paged":
            raise ValueError(
                "pangu_ultra_moe is served by the paged engine alone "
                f"(engine={engine!r}: text/generation.py's static engine "
                "has no latent cache)")
        from ...inference.engine import generate_paged

        ids = input_ids._data if hasattr(input_ids, "_data") else input_ids
        return generate_paged(self, np.asarray(ids), max_new_tokens, **kw)

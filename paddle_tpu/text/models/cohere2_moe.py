"""Cohere2-MoE decoder (`model_type: cohere2_moe`, Command A+): a
parallel-block decoder — attention and the expert layer both off ONE
bias-free LayerNorm, `x + attn(h) + moe(h)` — whose layers come in periods
of three sliding-window layers (interleaved RoPE, window 4096) to one
full-attention layer with no positional encoding, over a sigmoid top-k
router with shared experts averaged, and a tied head.

The Layer holds the parameters; the mathematics is `parallel_block.py`'s,
which the serving engine's programs call too. The expert layer may be one
chip's share of an expert-parallel deployment: `num_local_experts` held
from `expert_rank * num_local_experts` on, the router over all
`num_experts` (`incubate/nn/functional/dropless_moe.py`).

Serving: `ServingEngine(model)` (paged; two kinds of layer state). Not
served here, each refused by name: int8/int4 KV, weight-only
quantization, speculative decoding, the prefix cache, and
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
from . import parallel_block as pb


@dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096       # the width of ONE expert
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 200000
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    sliding_window: int = 4096
    #: one kind a layer; None: periods of `layer_switch`, local first
    layer_types: tuple | None = None
    layer_switch: int = 4
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    #: the experts held here: `num_local_experts` from
    #: `expert_rank * num_local_experts` on (None: all of them)
    num_local_experts: int | None = None
    expert_rank: int = 0
    logit_scale: float = 1.0
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                pb.FULL if (i + 1) % self.layer_switch == 0 else pb.SLIDING
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        bad = set(self.layer_types) - {pb.SLIDING, pb.FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_local_experts is None:
            self.num_local_experts = self.num_experts
        if self.first_expert + self.num_local_experts > self.num_experts:
            raise ValueError(
                f"rank {self.expert_rank} x {self.num_local_experts} held "
                f"experts passes num_experts {self.num_experts}")
        if not self.tie_word_embeddings:
            raise ValueError("cohere2_moe ties its head to the embedding")

    @property
    def first_expert(self) -> int:
        return self.expert_rank * self.num_local_experts

    def block_spec(self) -> pb.BlockSpec:
        return pb.BlockSpec(
            hidden_size=self.hidden_size,
            num_heads=self.num_attention_heads,
            num_kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
            eps=self.layer_norm_eps, rope_theta=float(self.rope_theta),
            window=self.sliding_window, layer_types=self.layer_types,
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            first_expert=self.first_expert,
            num_local_experts=self.num_local_experts,
            num_shared_experts=self.num_shared_experts,
            logit_scale=float(self.logit_scale))


def cohere2_moe_tiny_config(**kw) -> Cohere2MoeConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                num_hidden_layers=4, num_attention_heads=8,
                num_key_value_heads=2, head_dim=16,
                max_position_embeddings=256, sliding_window=16,
                num_experts=16, num_experts_per_tok=4,
                num_shared_experts=2)
    base.update(kw)
    return Cohere2MoeConfig(**base)


class _Weights(nn.Layer):
    """A bag of parameters: {name: shape}, all normal(0, std) — or ones
    for the names in `ones` (norm gains)."""

    def __init__(self, shapes: dict, config: Cohere2MoeConfig, ones=()):
        super().__init__()
        for name, shape in shapes.items():
            init = Constant(1.0) if name in ones \
                else Normal(0.0, config.initializer_range)
            setattr(self, name, self.create_parameter(
                list(shape), dtype=config.dtype, default_initializer=init))


class Cohere2MoeSparseMoe(nn.Layer):
    """The expert layer's parameters: the router over all experts, the
    held routed experts stacked, the shared experts side by side."""

    def __init__(self, c: Cohere2MoeConfig):
        super().__init__()
        h, f = c.hidden_size, c.intermediate_size
        n, s = c.num_local_experts, c.num_shared_experts
        self.router = _Weights({"weight": (h, c.num_experts)}, c)
        self.experts = _Weights({"gate_proj": (n, h, f),
                                 "up_proj": (n, h, f),
                                 "down_proj": (n, f, h)}, c)
        self.shared_experts = _Weights({"gate_proj": (h, s * f),
                                        "up_proj": (h, s * f),
                                        "down_proj": (s * f, h)}, c)


class Cohere2MoeDecoderLayer(nn.Layer):
    def __init__(self, c: Cohere2MoeConfig):
        super().__init__()
        h = c.hidden_size
        q, kv = (c.num_attention_heads * c.head_dim,
                 c.num_key_value_heads * c.head_dim)
        self.input_layernorm = _Weights({"weight": (h,)}, c,
                                        ones=("weight",))
        self.self_attn = _Weights({"q_proj": (h, q), "k_proj": (h, kv),
                                   "v_proj": (h, kv), "o_proj": (q, h)}, c)
        self.mlp = Cohere2MoeSparseMoe(c)

    def arrays(self, get) -> dict:
        """`get(parameter)` for each of the layer's parameters, under
        `parallel_block.block`'s names."""
        a, m = self.self_attn, self.mlp
        return {"ln": get(self.input_layernorm.weight),
                "q": get(a.q_proj), "k": get(a.k_proj),
                "v": get(a.v_proj), "o": get(a.o_proj),
                "router": get(m.router.weight),
                "experts_gate": get(m.experts.gate_proj),
                "experts_up": get(m.experts.up_proj),
                "experts_down": get(m.experts.down_proj),
                "shared_gate": get(m.shared_experts.gate_proj),
                "shared_up": get(m.shared_experts.up_proj),
                "shared_down": get(m.shared_experts.down_proj)}


class Cohere2MoeModel(nn.Layer):
    def __init__(self, c: Cohere2MoeConfig):
        super().__init__()
        self.embed_tokens = _Weights(
            {"weight": (c.vocab_size, c.hidden_size)}, c)
        self.layers = nn.LayerList(
            [Cohere2MoeDecoderLayer(c) for _ in range(c.num_hidden_layers)])
        self.norm = _Weights({"weight": (c.hidden_size,)}, c,
                             ones=("weight",))


@functools.partial(jax.jit, static_argnums=0)
def _forward(spec, params, ids):
    return jax.vmap(lambda row: pb.forward_sequence(params, row, spec))(ids)


class Cohere2MoeForCausalLM(nn.Layer):
    _gen_arch = "cohere2_moe"   # serving-engine layout (inference/layered.py)

    def __init__(self, config: Cohere2MoeConfig):
        super().__init__()
        self.config = config
        self.model = Cohere2MoeModel(config)

    def serving_arrays(self, get=lambda p: p._data) -> dict:
        """{"embed", "final_ln", "layers": [...]} under the names the
        step programs use; by default the parameters' own buffers, by
        reference (no copy is made)."""
        m = self.model
        return {"embed": get(m.embed_tokens.weight),
                "final_ln": get(m.norm.weight),
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
                      name="cohere2_moe_forward")
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
                "cohere2_moe is served by the paged engine alone "
                f"(engine={engine!r}: text/generation.py's static engine "
                "has no two-kind cache)")
        from ...inference.engine import generate_paged

        ids = input_ids._data if hasattr(input_ids, "_data") else input_ids
        return generate_paged(self, np.asarray(ids), max_new_tokens, **kw)

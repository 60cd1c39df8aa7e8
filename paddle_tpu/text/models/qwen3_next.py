"""Qwen3-Next decoder (`model_type: qwen3_next`): a hybrid of Gated
DeltaNet linear attention (three layers of every `full_attention_interval`)
and gated softmax attention over ordinary K/V pages on every fourth
layer (grouped query heads, zero-centred q/k norms, rotate-half RoPE on
the first `partial_rotary_factor` of each head's dims, a sigmoid output
gate carried inside `q_proj`), pre-norms with zero-centred gains, and on
every layer an expert FFN: softmax top-k over all `num_experts`,
renormalised over the k (`norm_topk_prob`), plus one shared expert
scaled by sigmoid(h w_sg); a head untied from the embedding.

The Layer holds the parameters, named and laid out as the published
checkpoint has them (W_qkvz and W_ba grouped by key head, each head's
query and gate side by side in `q_proj`); the mathematics is
`gated_delta_block.py`'s with a `gated_attention_block.BlockSpec` for the
full layers, which the serving engine's programs call too. The expert
layers may be one chip's share of an expert-parallel deployment:
`num_local_experts` held from `expert_rank * num_local_experts` on, the
router over all `num_experts`.

Serving: `ServingEngine(model)`: a linear layer keeps a float32 state a
slot (a matrix a value head, and its conv's last inputs), a full layer
K/V pages. Not served here, each refused by name: int8/int4 pages,
weight-only quantization, speculative decoding (the multi-token-prediction
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
from ...core.rng import next_key
from ...nn.initializer import Initializer
from . import gated_attention_block as ga
from . import gated_delta_block as gd
from . import latent_block as lb
from .pangu_ultra_moe import _gain, _Weights


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 512    # the width of ONE routed expert
    shared_expert_intermediate_size: int = 512
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    #: the experts held here: `num_local_experts` from
    #: `expert_rank * num_local_experts` on (None: all of them)
    num_local_experts: int | None = None
    expert_rank: int = 0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_local_experts is None:
            self.num_local_experts = self.num_experts
        if self.first_expert + self.num_local_experts > self.num_experts:
            raise ValueError(
                f"rank {self.expert_rank} x {self.num_local_experts} held "
                f"experts passes num_experts {self.num_experts}")
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear value heads must be a multiple of the "
                             "key heads (a value head reads key head "
                             "i // (nv / nk))")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of the KV heads")
        if self.rope_dim % 2:
            raise ValueError("the rotary dims must be even (rotate-half)")
        for key, want in (("tie_word_embeddings", False),
                          ("norm_topk_prob", True),
                          ("decoder_sparse_step", 1), ("mlp_only_layers", ())):
            if getattr(self, key) != want:
                raise ValueError(f"qwen3_next is written for {key}={want!r} "
                                 "(an expert FFN on every layer)")

    @property
    def first_expert(self) -> int:
        return self.expert_rank * self.num_local_experts

    @property
    def rope_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def attn_types(self) -> tuple:
        """Layer i is full where i + 1 is a multiple of the interval."""
        return tuple(gd.LINEAR if (i + 1) % self.full_attention_interval
                     else gd.FULL for i in range(self.num_hidden_layers))

    def block_spec(self) -> gd.BlockSpec:
        full = ga.BlockSpec(
            hidden_size=self.hidden_size,
            num_heads=self.num_attention_heads,
            num_kv_heads=self.num_key_value_heads, head_dim=self.head_dim,
            rope_dim=self.rope_dim, eps=self.rms_norm_eps,
            rope_theta=float(self.rope_theta),
            layer_types=(lb.EXPERTS,) * self.num_hidden_layers,
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            first_expert=self.first_expert,
            num_local_experts=self.num_local_experts)
        return gd.BlockSpec(
            full=full, attn_types=self.attn_types,
            num_k_heads=self.linear_num_key_heads,
            num_v_heads=self.linear_num_value_heads,
            k_dim=self.linear_key_head_dim,
            v_dim=self.linear_value_head_dim,
            conv_kernel=self.linear_conv_kernel_dim,
            o_eps=float(self.rms_norm_eps))


class LogUniform(Initializer):
    """log U(low, high): the Gated DeltaNet's initialiser of `A_log`."""

    def __init__(self, low, high):
        self.low, self.high = low, high

    def _generate(self, shape, dtype):
        return jnp.log(jax.random.uniform(next_key(), shape, jnp.float32,
                                          self.low, self.high)).astype(dtype)


class GatedDeltaNetWeights(_Weights):
    """A Gated DeltaNet layer's parameters, matrices [in, out]; `conv1d`
    is [K, channels] (tap j multiplies the input K - 1 - j positions
    back); `A_log` = log U(1, 16); `dt_bias` and the output norm's gain
    `norm` ones or zeros as the model names them in `ones` and `zeros`
    (Qwen3-Next: ones, its gain plain). `c`: any config with the
    `linear_*` sizes, `hidden_size`, `dtype` and `initializer_range`."""

    def __init__(self, c, ones=(), zeros=()):
        nk, nv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        super().__init__({
            "in_proj_qkvz": (c.hidden_size, 2 * nk * dk + 2 * nv * dv),
            "in_proj_ba": (c.hidden_size, 2 * nv),
            "conv1d": (c.linear_conv_kernel_dim, 2 * nk * dk + nv * dv),
            "dt_bias": (nv,), "norm": (dv,),
            "out_proj": (nv * dv, c.hidden_size)}, c, ones=ones,
            zeros=zeros)
        self.A_log = self.create_parameter(
            [nv], dtype=c.dtype, default_initializer=LogUniform(1.0, 16.0))

    def arrays(self, get) -> dict:
        return {"qkvz": get(self.in_proj_qkvz), "ba": get(self.in_proj_ba),
                "conv": get(self.conv1d), "A_log": get(self.A_log),
                "dt_bias": get(self.dt_bias), "o_norm": get(self.norm),
                "out": get(self.out_proj)}


class Qwen3NextSparseMoeBlock(nn.Layer):
    """The expert layer's parameters: the router `gate` over all experts,
    the held routed experts stacked, the shared expert and its gate."""

    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        h, f, fs = (c.hidden_size, c.moe_intermediate_size,
                    c.shared_expert_intermediate_size)
        n = c.num_local_experts
        self.gate = _Weights({"weight": (h, c.num_experts)}, c)
        self.experts = _Weights({"gate_proj": (n, h, f),
                                 "up_proj": (n, h, f),
                                 "down_proj": (n, f, h)}, c)
        self.shared_expert = _Weights({"gate_proj": (h, fs),
                                       "up_proj": (h, fs),
                                       "down_proj": (fs, h)}, c)
        self.shared_expert_gate = _Weights({"weight": (h, 1)}, c)

    def arrays(self, get) -> dict:
        return {"router": get(self.gate.weight),
                "experts_gate": get(self.experts.gate_proj),
                "experts_up": get(self.experts.up_proj),
                "experts_down": get(self.experts.down_proj),
                "shared_gate": get(self.shared_expert.gate_proj),
                "shared_up": get(self.shared_expert.up_proj),
                "shared_down": get(self.shared_expert.down_proj),
                "shared_expert_gate": get(self.shared_expert_gate.weight)}


class Qwen3NextDecoderLayer(nn.Layer):
    def __init__(self, c: Qwen3NextConfig, attn: str):
        super().__init__()
        h, nh, d = c.hidden_size, c.num_attention_heads, c.head_dim
        self.attn = attn
        self.input_layernorm = _gain(c, h, zero_centred=True)
        self.post_attention_layernorm = _gain(c, h, zero_centred=True)
        if attn == gd.FULL:
            nkv = c.num_key_value_heads
            self.self_attn = _Weights({
                "q_proj": (h, nh * 2 * d), "k_proj": (h, nkv * d),
                "v_proj": (h, nkv * d), "o_proj": (nh * d, h),
                "q_norm": (d,), "k_norm": (d,)}, c,
                zeros=("q_norm", "k_norm"))
        else:
            self.linear_attn = GatedDeltaNetWeights(
                c, ones=("dt_bias", "norm"))
        self.mlp = Qwen3NextSparseMoeBlock(c)

    def arrays(self, get) -> dict:
        """`get(parameter)` for each of the layer's parameters, under
        the block functions' names."""
        out = {"ln_in": get(self.input_layernorm.weight),
               "ln_ffn": get(self.post_attention_layernorm.weight),
               **self.mlp.arrays(get)}
        if self.attn == gd.FULL:
            a = self.self_attn
            out.update(q=get(a.q_proj), k=get(a.k_proj), v=get(a.v_proj),
                       o=get(a.o_proj), q_norm=get(a.q_norm),
                       k_norm=get(a.k_norm))
        else:
            out.update(self.linear_attn.arrays(get))
        return out


class Qwen3NextModel(nn.Layer):
    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        self.embed_tokens = _Weights(
            {"weight": (c.vocab_size, c.hidden_size)}, c)
        self.layers = nn.LayerList(
            [Qwen3NextDecoderLayer(c, attn) for attn in c.attn_types])
        self.norm = _gain(c, c.hidden_size, zero_centred=True)


@functools.partial(jax.jit, static_argnums=0)
def _forward(spec, params, ids):
    return jax.vmap(lambda row: gd.forward_sequence(params, row, spec))(ids)


class Qwen3NextForCausalLM(nn.Layer):
    _gen_arch = "qwen3_next"  # serving-engine layout (inference/layered.py)

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        self.model = Qwen3NextModel(config)
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
                      name="qwen3_next_forward")
        if labels is None:
            return out
        from ...nn import functional as F

        return F.cross_entropy(out.reshape([-1, self.config.vocab_size]),
                               labels.reshape([-1]))

    def generate(self, input_ids, max_new_tokens=32, engine="paged", **kw):
        """Greedy or sampled continuation through the paged serving
        engine; the static single-program engine has no recurrent
        state."""
        if engine != "paged":
            raise ValueError(
                "qwen3_next is served by the paged engine alone "
                f"(engine={engine!r}: text/generation.py's static engine "
                "has no recurrent state)")
        from ...inference.engine import generate_paged

        ids = input_ids._data if hasattr(input_ids, "_data") else input_ids
        return generate_paged(self, np.asarray(ids), max_new_tokens, **kw)

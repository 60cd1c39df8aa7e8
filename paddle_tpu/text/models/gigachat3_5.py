"""GigaChat3.5 decoder (`model_type: gigachat3_5`): a hybrid of Gated
DeltaNet linear attention (three layers of every four) and latent
attention (MLA, `full_attention_layers`), sandwich ("pre_post") norms with
zero-centred gains, `first_k_dense_replace` leading layers with a dense
SwiGLU and expert layers after them (sigmoid top-k over all experts,
normalised and scaled by `routed_scaling_factor`, one shared expert), every
SwiGLU clamped at `swiglu_limit`, and a head untied from the embedding.

The Layer holds the parameters; the mathematics is
`gated_delta_block.py`'s (and, for the MLA layers, `latent_block.py`'s
with GigaChat's options: interleaved YaRN RoPE, the mscale^2 softmax
factor, the gated attention output), which the serving engine's programs
call too. The expert layers may be one chip's share of an expert-parallel
deployment: `num_local_experts` held from `expert_rank *
num_local_experts` on, the router over all `n_routed_experts`.

Serving: `ServingEngine(model)`: a linear layer keeps a float32 state a
slot (a matrix a value head, and its conv's last inputs), the MLA layers
a paged latent pool. Not served here, each refused by name: int8/int4
pools, weight-only quantization, speculative decoding (the config's two
multi-token-prediction modules, its drafters, are not built), the prefix
cache, and `text/generation.py`'s static engine.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ... import nn
from ...core.dispatch import op_call
from ...core.rng import next_key
from ...nn.initializer import Initializer
from . import gated_delta_block as gd
from . import latent_block as lb
from .pangu_ultra_moe import (PanguUltraMoESparseMoe, _attention, _gain,
                              _Weights)


def _yarn():
    return {"type": "yarn", "factor": 8.0, "beta_fast": 32.0,
            "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0,
            "original_max_position_embeddings": 32768}


@dataclass
class GigaChat35Config:
    vocab_size: int = 128256
    hidden_size: int = 7168
    intermediate_size: int = 18432      # the dense layers' FFN
    moe_intermediate_size: int = 2048   # the width of ONE expert
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 3
    full_attention_layers: tuple = tuple(range(3, 40, 4))
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 100000.0
    rope_scaling: dict = field(default_factory=_yarn)
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    swiglu_limit: float = 10.0
    linear_num_key_heads: int = 32
    linear_num_value_heads: int = 64
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_sigmoid_gate_scale: float = 2.0
    linear_attn_o_norm_eps: float = 1e-6
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
        self.full_attention_layers = tuple(
            int(i) for i in self.full_attention_layers)
        if any(not 0 <= i < self.num_hidden_layers
               for i in self.full_attention_layers):
            raise ValueError(f"full_attention_layers "
                             f"{self.full_attention_layers} outside "
                             f"{self.num_hidden_layers} layers")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear value heads must be a multiple of the "
                             "key heads (a value head reads key head "
                             "i // (nv / nk))")
        for key, want in (("tie_word_embeddings", False),
                          ("norm_topk_prob", True)):
            if getattr(self, key) is not want:
                raise ValueError(f"gigachat3_5 is written for {key}={want}")

    @property
    def first_expert(self) -> int:
        return self.expert_rank * self.num_local_experts

    @property
    def attn_types(self) -> tuple:
        return tuple(gd.FULL if i in self.full_attention_layers
                     else gd.LINEAR for i in range(self.num_hidden_layers))

    @property
    def ffn_types(self) -> tuple:
        return tuple(lb.DENSE if i < self.first_k_dense_replace
                     else lb.EXPERTS for i in range(self.num_hidden_layers))

    @property
    def mscale(self) -> float:
        """YaRN's attention factor 0.1 ln(factor) + 1 (mscale_all_dim 1),
        squared into the softmax scale (`use_mla_scaling_factor`: the
        published config's, and so this model's, always)."""
        r = self.rope_scaling or {}
        factor = float(r.get("factor", 1.0))
        if factor <= 1.0:
            return 1.0
        return 0.1 * float(r.get("mscale_all_dim", 1.0)) * math.log(factor) \
            + 1.0

    def block_spec(self) -> gd.BlockSpec:
        r = self.rope_scaling or {}
        yarn = () if not r else (
            float(r["factor"]), int(r["original_max_position_embeddings"]),
            float(r["beta_fast"]), float(r["beta_slow"]))
        mla = lb.BlockSpec(
            hidden_size=self.hidden_size,
            num_heads=self.num_attention_heads,
            qk_nope_dim=self.qk_nope_head_dim,
            qk_rope_dim=self.qk_rope_head_dim, v_dim=self.v_head_dim,
            q_rank=self.q_lora_rank, kv_rank=self.kv_lora_rank,
            eps=self.rms_norm_eps, rope_theta=float(self.rope_theta),
            layer_types=self.ffn_types,
            num_experts=self.n_routed_experts,
            top_k=self.num_experts_per_tok,
            first_expert=self.first_expert,
            num_local_experts=self.num_local_experts,
            num_shared_experts=self.n_shared_experts,
            routed_scale=float(self.routed_scaling_factor),
            rope_interleave=True, rope_yarn=yarn, mscale=self.mscale,
            gated_attention=True, zero_centred=True,
            swiglu_limit=float(self.swiglu_limit))
        return gd.BlockSpec(
            mla=mla, attn_types=self.attn_types,
            num_k_heads=self.linear_num_key_heads,
            num_v_heads=self.linear_num_value_heads,
            k_dim=self.linear_key_head_dim,
            v_dim=self.linear_value_head_dim,
            conv_kernel=self.linear_conv_kernel_dim,
            o_eps=float(self.linear_attn_o_norm_eps),
            gate_scale=float(self.linear_sigmoid_gate_scale))


class _LogUniform(Initializer):
    """log U(low, high): Qwen3-Next's initialiser of `A_log`."""

    def __init__(self, low, high):
        self.low, self.high = low, high

    def _generate(self, shape, dtype):
        return jnp.log(jax.random.uniform(next_key(), shape, jnp.float32,
                                          self.low, self.high)).astype(dtype)


class _LinearAttention(_Weights):
    """A Gated DeltaNet layer's parameters, matrices [in, out]; `conv1d`
    is [K, channels] (tap j multiplies the input K - 1 - j positions
    back); `A_log` = log U(1, 16) and `dt_bias` 0 (Qwen3-Next's
    initialiser), `norm` the zero-centred output norm's w."""

    def __init__(self, c: GigaChat35Config):
        nk, nv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        super().__init__({
            "in_proj_qkvz": (c.hidden_size, 2 * nk * dk + 2 * nv * dv),
            "in_proj_ba": (c.hidden_size, 2 * nv),
            "conv1d": (c.linear_conv_kernel_dim, 2 * nk * dk + nv * dv),
            "dt_bias": (nv,), "norm": (dv,),
            "out_proj": (nv * dv, c.hidden_size)}, c,
            zeros=("dt_bias", "norm"))
        self.A_log = self.create_parameter(
            [nv], dtype=c.dtype, default_initializer=_LogUniform(1.0, 16.0))


class GigaChat35DecoderLayer(nn.Layer):
    def __init__(self, c: GigaChat35Config, kind):
        super().__init__()
        h = c.hidden_size
        self.kind = kind
        self.input_layernorm = _gain(c, h, zero_centred=True)
        self.post_attention_layernorm = _gain(c, h, zero_centred=True)
        self.pre_mlp_layernorm = _gain(c, h, zero_centred=True)
        self.post_mlp_layernorm = _gain(c, h, zero_centred=True)
        if kind[0] == gd.FULL:
            self.self_attn = _attention(c, zero_centred=True, gated=True)
        else:
            self.linear_attn = _LinearAttention(c)
        if kind[1] == lb.DENSE:
            f = c.intermediate_size
            self.mlp = _Weights({"gate_proj": (h, f), "up_proj": (h, f),
                                 "down_proj": (f, h)}, c)
        else:
            self.mlp = PanguUltraMoESparseMoe(c)

    def arrays(self, get) -> dict:
        """`get(parameter)` for each of the layer's parameters, under
        the block functions' names."""
        m = self.mlp
        out = {"ln_in": get(self.input_layernorm.weight),
               "ln_post_attn": get(self.post_attention_layernorm.weight),
               "ln_pre_ffn": get(self.pre_mlp_layernorm.weight),
               "ln_post_ffn": get(self.post_mlp_layernorm.weight)}
        if self.kind[0] == gd.FULL:
            a = self.self_attn
            out.update(q_a=get(a.q_a_proj), q_a_ln=get(a.q_a_layernorm),
                       q_b=get(a.q_b_proj), kv_a=get(a.kv_a_proj_with_mqa),
                       kv_a_ln=get(a.kv_a_layernorm), kv_b=get(a.kv_b_proj),
                       o=get(a.o_proj))
            out["attn_gate"] = get(a.gate_proj)
        else:
            w = self.linear_attn
            out.update(qkvz=get(w.in_proj_qkvz), ba=get(w.in_proj_ba),
                       conv=get(w.conv1d), A_log=get(w.A_log),
                       dt_bias=get(w.dt_bias), o_norm=get(w.norm),
                       out=get(w.out_proj))
        if self.kind[1] == lb.DENSE:
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


class GigaChat35Model(nn.Layer):
    def __init__(self, c: GigaChat35Config):
        super().__init__()
        self.embed_tokens = _Weights(
            {"weight": (c.vocab_size, c.hidden_size)}, c)
        self.layers = nn.LayerList(
            [GigaChat35DecoderLayer(c, kind)
             for kind in zip(c.attn_types, c.ffn_types)])
        self.norm = _gain(c, c.hidden_size, zero_centred=True)


@functools.partial(jax.jit, static_argnums=0)
def _forward(spec, params, ids):
    return jax.vmap(lambda row: gd.forward_sequence(params, row, spec))(ids)


class GigaChat35ForCausalLM(nn.Layer):
    _gen_arch = "gigachat3_5"  # serving-engine layout (inference/layered.py)

    def __init__(self, config: GigaChat35Config):
        super().__init__()
        self.config = config
        self.model = GigaChat35Model(config)
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
                      name="gigachat3_5_forward")
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
                "gigachat3_5 is served by the paged engine alone "
                f"(engine={engine!r}: text/generation.py's static engine "
                "has no recurrent state)")
        from ...inference.engine import generate_paged

        ids = input_ids._data if hasattr(input_ids, "_data") else input_ids
        return generate_paged(self, np.asarray(ids), max_new_tokens, **kw)

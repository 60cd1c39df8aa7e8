"""Family `gigachat3_5`: the hybrid decoder of GigaChat3.5-432B-A28B
(`model_type: gigachat3_5`) through the repo's `GigaChat35ForCausalLM`, as
ONE chip's share of an expert-parallel deployment.

    ZRMS(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)        every gain zero-centred
    linear layer (Gated DeltaNet):
      h           = ZRMS(x; w_in)
      q,k | v | z = h W_qkvz ;  b | a = h W_ba            q, k: nk x dk;  v, z: nv x dv
      q,k,v       = silu(sum_j w_j x_{t-K+1+j})            causal depthwise conv, zeros before 0
      q = l2norm(q) / sqrt(dk) ;  k = l2norm(k)           value head i reads key head i // (nv / nk)
      beta = sigmoid(b) ;  g = -exp(A_log) softplus(a + dt_bias)
      S_t = e^g_t S_{t-1} + k_t (beta_t (v_t - e^g_t S_{t-1}^T k_t))^T      S_0 = 0, token by token
      o_t = S_t^T q_t ;  o = RMS(o; o_eps) (1 + w_o) 2 sigmoid(z)   a head
      x1  = x + ZRMS(o W_out; w_post_attn)
    MLA layer (`full_attention_layers`):
      cq = ZRMS(h Wqa) ;  q = cq Wqb -> [nh, dn + dr] ;  ckv|kr = h Wkva ;  c = ZRMS(ckv)
      RoPE on q_rope and kr: pairs (2i, 2i + 1), YaRN frequencies
      k^h = [c Wuk^h | kr] ,  v^h = c Wuv^h
      a   = softmax_{j<=t}(q^h . k_j^h * mscale^2 / sqrt(dn + dr)) v^h
      a   = a * sigmoid(h Wgate)                       a channel, before Wo
      x1  = x + ZRMS(a Wo; w_post_attn)
    FFN: h2 = ZRMS(x1; w_pre_ffn);  SwiGLU(h) = Wd(silu(min(Wg h, L)) * clip(Wu h, -L, L))
      dense layers:  f = SwiGLU(h2)
      else:  sc = sigmoid(h2 Wr) in R^E ;  T = the k largest ;  g_e = s sc_e / sum_T sc
             f = sum_{e in T, e HELD HERE} g_e SwiGLU_e(h2) + SwiGLU'(h2)
      x'  = x1 + ZRMS(f; w_post_ffn)
    logits = ZRMS(x_L; w_f) Wlm^T                  Wlm untied

What the benchmark owns of the family: the configuration file -> the
program's model (built under `paddle.LazyGuard()`), the weights' names
and shapes, the operations a token needs, and the plain float32 reference
with its fp8 control. The reference imports nothing of the program: it
runs the linear layers by their recurrent definition, token by token,
and is given the same share of the experts as the program.

Departures from the published description: the configuration file's
`assumed`.
"""
from __future__ import annotations

import numpy as np

from .cohere2_moe import _mm

DEPTH_KEY = "num_hidden_layers"
LINEAR, FULL = "linear", "full"
DENSE, EXPERTS = "dense", "experts"


def depth(cfg: dict, role: str) -> int:
    return int(cfg["num_hidden_layers"][role])


def _sizes(cfg: dict) -> dict:
    ep = cfg["expert_parallel"]
    return {"h": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "fe": cfg["moe_intermediate_size"], "v": cfg["vocab_size"],
            "nh": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "rq": cfg["q_lora_rank"],
            "rkv": cfg["kv_lora_rank"],
            "nk": cfg["linear_num_key_heads"],
            "nv": cfg["linear_num_value_heads"],
            "lk": cfg["linear_key_head_dim"],
            "lv": cfg["linear_value_head_dim"],
            "conv": cfg["linear_conv_kernel_dim"],
            "experts": int(ep["experts_total"]),
            "held": int(cfg["n_routed_experts"]),
            "first": int(ep["rank"]) * int(cfg["n_routed_experts"]),
            "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"],
            "scale": float(cfg["routed_scaling_factor"]),
            "limit": float(cfg["swiglu_limit"]),
            "eps": cfg["rms_norm_eps"]}


def layer_kinds(cfg: dict, layers: int) -> list:
    """[(attention, FFN)] a layer: `full_attention_layers` are MLA."""
    full = set(cfg["full_attention_layers"])
    return [(FULL if i in full else LINEAR,
             DENSE if i < cfg["first_k_dense_replace"] else EXPERTS)
            for i in range(layers)]


def _conv_dim(z: dict) -> int:
    return 2 * z["nk"] * z["lk"] + z["nv"] * z["lv"]


def weight_spec(cfg: dict, layers: int) -> list:
    """[(name, shape, init)] in the order the weights are made; names are
    the program's `named_parameters()` names, matrices are [in, out]. The
    embedding and the head FIRST: `weights.make` draws float32 before it
    casts, and their 3.7 GB each fit only while little else has been made.
    Zero-centred gains are w of ZRMS's (1 + w), made normal(0, 0.02) so
    that the program has to read them; `A_log` and `dt_bias` zeros (A =
    1: `assumed.initializer`)."""
    z = _sizes(cfg)
    h, nh, nv = z["h"], z["nh"], z["nv"]
    spec = [("model.embed_tokens.weight", (z["v"], h), "normal"),
            ("lm_head.weight", (z["v"], h), "normal")]
    for i, (attn, ffn) in enumerate(layer_kinds(cfg, layers)):
        p = f"model.layers.{i}."
        spec += [(p + "input_layernorm.weight", (h,), "normal"),
                 (p + "post_attention_layernorm.weight", (h,), "normal"),
                 (p + "pre_mlp_layernorm.weight", (h,), "normal"),
                 (p + "post_mlp_layernorm.weight", (h,), "normal")]
        if attn == LINEAR:
            a = p + "linear_attn."
            spec += [(a + "in_proj_qkvz",
                      (h, 2 * z["nk"] * z["lk"] + 2 * nv * z["lv"]),
                      "normal"),
                     (a + "in_proj_ba", (h, 2 * nv), "normal"),
                     (a + "conv1d", (z["conv"], _conv_dim(z)), "normal"),
                     (a + "A_log", (nv,), "zeros"),
                     (a + "dt_bias", (nv,), "zeros"),
                     (a + "norm", (z["lv"],), "normal"),
                     (a + "out_proj", (nv * z["lv"], h), "normal")]
        else:
            a = p + "self_attn."
            spec += [(a + "q_a_proj", (h, z["rq"]), "normal"),
                     (a + "q_a_layernorm", (z["rq"],), "normal"),
                     (a + "q_b_proj", (z["rq"], nh * (z["dn"] + z["dr"])),
                      "normal"),
                     (a + "kv_a_proj_with_mqa", (h, z["rkv"] + z["dr"]),
                      "normal"),
                     (a + "kv_a_layernorm", (z["rkv"],), "normal"),
                     (a + "kv_b_proj", (z["rkv"], nh * (z["dn"] + z["dv"])),
                      "normal"),
                     (a + "o_proj", (nh * z["dv"], h), "normal"),
                     (a + "gate_proj", (h, nh * z["dv"]), "normal")]
        if ffn == DENSE:
            f = z["f"]
            spec += [(p + "mlp.gate_proj", (h, f), "normal"),
                     (p + "mlp.up_proj", (h, f), "normal"),
                     (p + "mlp.down_proj", (f, h), "normal")]
        else:
            f, n, s = z["fe"], z["held"], z["shared"]
            spec += [
                (p + "mlp.router.weight", (h, z["experts"]), "normal"),
                (p + "mlp.experts.gate_proj", (n, h, f), "normal"),
                (p + "mlp.experts.up_proj", (n, h, f), "normal"),
                (p + "mlp.experts.down_proj", (n, f, h), "normal"),
                (p + "mlp.shared_experts.gate_proj", (h, s * f), "normal"),
                (p + "mlp.shared_experts.up_proj", (h, s * f), "normal"),
                (p + "mlp.shared_experts.down_proj", (s * f, h), "normal")]
    spec.append(("model.norm.weight", (h,), "normal"))
    return spec


def build_model(cfg: dict, layers: int, role: str):
    """The program's model in the configuration's dtype, built under
    `paddle.LazyGuard()`: shapes and no buffers (`serve.py` assigns the
    seed's weights next; an eager float32 initialisation would not fit)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import (GigaChat35Config,
                                        GigaChat35ForCausalLM)

    if role != "serve":
        raise ValueError(f"{cfg['name']} is cut for serving; role {role!r} "
                         "has no depth in its file")
    z = _sizes(cfg)
    mcfg = GigaChat35Config(
        vocab_size=z["v"], hidden_size=z["h"], intermediate_size=z["f"],
        moe_intermediate_size=z["fe"], num_hidden_layers=layers,
        first_k_dense_replace=min(cfg["first_k_dense_replace"], layers),
        full_attention_layers=tuple(
            i for i in cfg["full_attention_layers"] if i < layers),
        num_attention_heads=z["nh"], q_lora_rank=z["rq"],
        kv_lora_rank=z["rkv"], qk_nope_head_dim=z["dn"],
        qk_rope_head_dim=z["dr"], v_head_dim=z["dv"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=z["eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=dict(cfg["rope_scaling"]),
        n_routed_experts=z["experts"], n_shared_experts=z["shared"],
        num_experts_per_tok=z["k"], routed_scaling_factor=z["scale"],
        norm_topk_prob=cfg["norm_topk_prob"], swiglu_limit=z["limit"],
        linear_num_key_heads=z["nk"], linear_num_value_heads=z["nv"],
        linear_key_head_dim=z["lk"], linear_value_head_dim=z["lv"],
        linear_conv_kernel_dim=z["conv"],
        linear_sigmoid_gate_scale=cfg["linear_sigmoid_gate_scale"],
        linear_attn_o_norm_eps=cfg["linear_attn_o_norm_eps"],
        num_local_experts=z["held"],
        expert_rank=int(cfg["expert_parallel"]["rank"]),
        tie_word_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"])
    with paddle.LazyGuard():
        model = GigaChat35ForCausalLM(mcfg)
    model.eval()
    return model


# ----------------------------------------------------------- operations

def attention_shape(cfg: dict) -> dict:
    """What a decode over the latent cache moves a token (the MLA
    layers): `heads` queries of `latent_dim + rope_dim` against ONE
    cached row."""
    return {"heads": cfg["num_attention_heads"], "kv_heads": 1,
            "head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "v_head_dim": cfg["v_head_dim"],
            "latent_dim": cfg["kv_lora_rank"],
            "rope_dim": cfg["qk_rope_head_dim"]}


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    """An MLA layer's cached row: the latent and the rotary key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def state_shape(cfg: dict) -> tuple:
    """A linear layer's recurrent state a slot: [nv, dk, dv] float32."""
    z = _sizes(cfg)
    return z["nv"], z["lk"], z["lv"]


def state_flops_per_token_layer(cfg: dict) -> float:
    """The recurrence a token a linear layer, by its definition: per
    element of S the decay (1), S^T k (2), k u^T added (2), S^T q (2)."""
    nv, dk, dv = state_shape(cfg)
    return 7.0 * nv * dk * dv


def attn_flops_per_layer(cfg: dict, q_tokens: float, ctx_sum: float) -> float:
    """An MLA layer in the published (expanded) form: QK^T over dn + dr
    and PV over dv, 2 FLOPs x heads a pair."""
    del q_tokens
    z = _sizes(cfg)
    return 2.0 * z["nh"] * (z["dn"] + z["dr"] + z["dv"]) * ctx_sum


def linear_params(cfg: dict) -> int:
    """A linear layer's products a token: W_qkvz, W_ba, the depthwise
    conv (K a channel) and W_out."""
    z = _sizes(cfg)
    h, nv = z["h"], z["nv"]
    return (h * (2 * z["nk"] * z["lk"] + 2 * nv * z["lv"]) + h * 2 * nv
            + z["conv"] * _conv_dim(z) + nv * z["lv"] * h)


def mla_params(cfg: dict) -> int:
    """An MLA layer's products a token, its output gate included."""
    z = _sizes(cfg)
    h, nh = z["h"], z["nh"]
    return (h * z["rq"] + z["rq"] * nh * (z["dn"] + z["dr"])
            + h * (z["rkv"] + z["dr"]) + z["rkv"] * nh * (z["dn"] + z["dv"])
            + nh * z["dv"] * h + h * nh * z["dv"])


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_params(cfg: dict, layers: int) -> float:
    """Parameters a token multiplies in the blocks, the routed term by
    EXPECTATION: of its k picks over E experts, k x held / E land on this
    chip. Wkvb counts once a token, as the published form has it."""
    z = _sizes(cfg)
    dense = 3 * z["h"] * z["f"]
    sparse = (z["shared"] * expert_params(cfg) + z["h"] * z["experts"]
              + z["k"] * z["held"] / z["experts"] * expert_params(cfg))
    return sum((linear_params(cfg) if attn == LINEAR else mla_params(cfg))
               + (dense if ffn == DENSE else sparse)
               for attn, ffn in layer_kinds(cfg, layers))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def serve_flops(cfg: dict, layers: int, tokens: float, logit_rows: float,
                ctx_sum: float) -> float:
    """Forward only: `tokens` through the blocks and each linear layer's
    recurrence, `logit_rows` through the head, attention over `ctx_sum`
    attended keys in every MLA layer."""
    kinds = layer_kinds(cfg, layers)
    n_full = sum(a == FULL for a, _ in kinds)
    return (2.0 * matmul_params(cfg, layers) * tokens
            + 2.0 * head_params(cfg) * logit_rows
            + n_full * attn_flops_per_layer(cfg, tokens, ctx_sum)
            + (layers - n_full) * state_flops_per_token_layer(cfg) * tokens)


# -------------------------------------------------------------- reference

def _rope_tables(cfg: dict, s: int):
    """cos, sin [S, dr / 2] float32 of pair i = dimensions (2i, 2i + 1),
    YaRN's frequencies (DeepSeek-V3's `yarn_find_correction_range` and
    linear ramp), angles worked out in float64."""
    dr, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    r = cfg["rope_scaling"]
    extra = 1.0 / (base ** (np.arange(0, dr, 2, dtype=np.float64) / dr))
    inter = extra / float(r["factor"])
    orig = float(r["original_max_position_embeddings"])

    def dim(rot):
        return dr * np.log(orig / (rot * 2 * np.pi)) / (2 * np.log(base))

    low = max(np.floor(dim(float(r["beta_fast"]))), 0)
    high = min(np.ceil(dim(float(r["beta_slow"]))), dr - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dr // 2) - low) / (high - low), 0, 1)
    inv = inter * (1 - mask) + extra * mask
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def mscale(cfg: dict) -> float:
    """YaRN's 0.1 ln(factor) + 1 at mscale_all_dim 1: the softmax scale
    is mscale^2 / sqrt(dn + dr) (`use_mla_scaling_factor`)."""
    r = cfg["rope_scaling"]
    return 0.1 * float(r["mscale_all_dim"]) * float(np.log(r["factor"])) + 1


_COMMON = {"ln_in": "input_layernorm.weight",
           "ln_post_attn": "post_attention_layernorm.weight",
           "ln_pre_ffn": "pre_mlp_layernorm.weight",
           "ln_post_ffn": "post_mlp_layernorm.weight"}
_ATTN_KEYS = {
    LINEAR: {"qkvz": "linear_attn.in_proj_qkvz",
             "ba": "linear_attn.in_proj_ba",
             "conv": "linear_attn.conv1d", "A_log": "linear_attn.A_log",
             "dt_bias": "linear_attn.dt_bias", "o_norm": "linear_attn.norm",
             "out": "linear_attn.out_proj"},
    FULL: {"q_a": "self_attn.q_a_proj", "q_a_ln": "self_attn.q_a_layernorm",
           "q_b": "self_attn.q_b_proj",
           "kv_a": "self_attn.kv_a_proj_with_mqa",
           "kv_a_ln": "self_attn.kv_a_layernorm",
           "kv_b": "self_attn.kv_b_proj", "o": "self_attn.o_proj",
           "gate": "self_attn.gate_proj"}}
_FFN_KEYS = {
    DENSE: {"gate": "mlp.gate_proj", "up": "mlp.up_proj",
            "down": "mlp.down_proj"},
    EXPERTS: {"router": "mlp.router.weight",
              "gate": "mlp.experts.gate_proj", "up": "mlp.experts.up_proj",
              "down": "mlp.experts.down_proj",
              "sgate": "mlp.shared_experts.gate_proj",
              "sup": "mlp.shared_experts.up_proj",
              "sdown": "mlp.shared_experts.down_proj"}}

#: query rows the reference works on at once, and heads: the float32
#: scores held are [HEADS, ROWS, S] = 134 MB at 16 x 256 x 8192
ROWS = 256
HEADS = 16
#: rows of the head the logits multiply at once (460 MB in float32)
VOCAB_ROWS = 16032


def _whole(n: int, step: int, what: str) -> int:
    step = n if n <= step else step
    if n % step:
        raise ValueError(f"{what} {n} is not whole blocks of {step}")
    return step


def _zrms(x, w, eps):
    import jax
    import jax.numpy as jnp

    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + w.astype(jnp.float32))


def _linear(cfg: dict, mm):
    """x [S, H] float32 -> x1: the Gated DeltaNet sublayer, its
    recurrence token by token from a zero state."""
    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    nk, nv, dk, dv, eps = z["nk"], z["nv"], z["lk"], z["lv"], z["eps"]
    kk = z["conv"]

    def l2norm(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    def linear(x, lw):
        s_len = x.shape[0]
        h = _zrms(x, lw["ln_in"], eps)
        qkvz = mm(h, lw["qkvz"])
        ba = mm(h, lw["ba"])
        cd = 2 * nk * dk + nv * dv
        xs, zg = qkvz[:, :cd], qkvz[:, cd:].reshape(s_len, nv, dv)
        w = lw["conv"].astype(jnp.float32)
        xp = jnp.pad(xs, ((kk - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(w[j] * xp[j:j + s_len] for j in range(kk)))
        q = l2norm(mixed[:, :nk * dk].reshape(s_len, nk, dk)) * dk ** -0.5
        k = l2norm(mixed[:, nk * dk:2 * nk * dk].reshape(s_len, nk, dk))
        v = mixed[:, 2 * nk * dk:].reshape(s_len, nv, dv)
        head_of = np.arange(nv) // (nv // nk)          # value head -> key head
        q, k = q[:, head_of], k[:, head_of]
        beta = jax.nn.sigmoid(ba[:, :nv])
        g = -jnp.exp(lw["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[:, nv:] + lw["dt_bias"].astype(jnp.float32))

        def token(st, xs_t):
            q_t, k_t, v_t, b_t, g_t = xs_t
            decayed = jnp.exp(g_t)[:, None, None] * st
            u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", decayed, k_t))
            st = decayed + jnp.einsum("hk,hv->hkv", k_t, u)
            return st, jnp.einsum("hkv,hk->hv", st, q_t)

        _, o = jax.lax.scan(token, jnp.zeros((nv, dk, dv), jnp.float32),
                            (q, k, v, beta, g))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg["linear_attn_o_norm_eps"])
        o = o * (1.0 + lw["o_norm"].astype(jnp.float32)) * (
            cfg["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(zg))
        out = mm(o.reshape(s_len, nv * dv), lw["out"])
        return x + _zrms(out, lw["ln_post_attn"], eps)

    return linear


def _interleaved_rope(a, cos, sin):
    """Pairs (2i, 2i + 1) of the last axis of a [S, ..., dr] rotated."""
    import jax.numpy as jnp

    shape = (cos.shape[0],) + (1,) * (a.ndim - 2) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    even, odd = a[..., 0::2], a[..., 1::2]
    return jnp.stack([even * c - odd * s, odd * c + even * s],
                     axis=-1).reshape(a.shape)


def _mla(cfg: dict, mm):
    """x [S, H] float32 -> x1: the latent attention of the whole sequence,
    `HEADS` heads at a time (their keys and values expanded from the
    latent), the query rows `ROWS` at a time."""
    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    nh, dn, dr, dv, eps = z["nh"], z["dn"], z["dr"], z["dv"], z["eps"]
    rq, rkv = z["rq"], z["rkv"]
    scale = float(mscale(cfg) ** 2 / np.sqrt(dn + dr))

    def attention(x, lw, cos, sin):
        s_len = x.shape[0]
        rows = _whole(s_len, ROWS, "sequence")
        g = _whole(nh, HEADS, "head count")
        h = _zrms(x, lw["ln_in"], eps)
        cq = _zrms(mm(h, lw["q_a"]), lw["q_a_ln"], eps)
        ckv = mm(h, lw["kv_a"])
        c = _zrms(ckv[:, :rkv], lw["kv_a_ln"], eps)
        kr = _interleaved_rope(ckv[:, rkv:], cos, sin)          # [S, dr]

        def groups(w, width):
            return w.reshape(w.shape[0], nh // g, g * width).transpose(1, 0, 2)

        wq, wkv = groups(lw["q_b"], dn + dr), groups(lw["kv_b"], dn + dv)
        wgate = groups(lw["gate"], dv)
        wo = lw["o"].reshape(nh // g, g * dv, -1)
        kv_pos = jnp.arange(s_len)

        def group(acc, ws):
            wq_g, wkv_g, wgate_g, wo_g = ws
            q = mm(cq, wq_g).reshape(s_len, g, dn + dr)
            q_nope = q[..., :dn]
            q_rope = _interleaved_rope(q[..., dn:], cos, sin)
            kv = mm(c, wkv_g).reshape(s_len, g, dn + dv)
            k_nope, v = kv[..., :dn], kv[..., dn:]

            def some_rows(start):
                qn = jax.lax.dynamic_slice_in_dim(q_nope, start, rows)
                qr = jax.lax.dynamic_slice_in_dim(q_rope, start, rows)
                pos = start + jnp.arange(rows)
                sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                      + jnp.einsum("qhd,kd->hqk", qr, kr)) * scale
                sc = jnp.where((pos[:, None] >= kv_pos[None, :])[None],
                               sc, -jnp.inf)
                return jnp.einsum("hqk,khd->qhd",
                                  jax.nn.softmax(sc, axis=-1), v)

            a = jax.lax.map(some_rows, jnp.arange(0, s_len, rows))
            a = a.reshape(s_len, g * dv) * jax.nn.sigmoid(mm(h, wgate_g))
            return acc + mm(a, wo_g), None

        a, _ = jax.lax.scan(group, jnp.zeros_like(x), (wq, wkv, wgate, wo))
        return x + _zrms(a, lw["ln_post_attn"], eps)

    return attention


def _ffn(cfg: dict, mm, kind: str):
    """x1 [S, H] float32 -> x1 + ZRMS(ffn(ZRMS(x1))), `ROWS` rows at a
    time (the held experts one at a time)."""
    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    eps, fe, lim = z["eps"], z["fe"], z["limit"]

    def swiglu(h, wg, wu, wd):
        return mm(jax.nn.silu(jnp.minimum(mm(h, wg), lim))
                  * jnp.clip(mm(h, wu), -lim, lim), wd)

    def ffn(x1, lw):
        s_len = x1.shape[0]
        rows = _whole(s_len, ROWS, "sequence")

        def some_rows(start):
            xr = jax.lax.dynamic_slice_in_dim(x1, start, rows)
            hr = _zrms(xr, lw["ln_pre_ffn"], eps)
            if kind == DENSE:
                f = swiglu(hr, lw["gate"], lw["up"], lw["down"])
            else:
                score = jax.nn.sigmoid(mm(hr, lw["router"]))    # [R, E]
                top, idx = jax.lax.top_k(score, z["k"])
                g = z["scale"] * top / jnp.sum(top, axis=-1, keepdims=True)

                def expert(acc, xs):
                    e, wg, wu, wd = xs
                    ge = jnp.sum(jnp.where(idx == z["first"] + e, g, 0.0),
                                 axis=-1)                       # [R]
                    return acc + ge[:, None] * swiglu(hr, wg, wu, wd), None

                f, _ = jax.lax.scan(
                    expert, jnp.zeros_like(xr),
                    (jnp.arange(z["held"]), lw["gate"], lw["up"],
                     lw["down"]))
                for j in range(z["shared"]):
                    cols = slice(j * fe, (j + 1) * fe)
                    f = f + swiglu(hr, lw["sgate"][:, cols],
                                   lw["sup"][:, cols], lw["sdown"][cols, :])
            return xr + _zrms(f, lw["ln_post_ffn"], eps)

        out = jax.lax.map(some_rows, jnp.arange(0, s_len, rows))
        return out.reshape(s_len, -1)

    return ffn


def _head(cfg: dict, mm):
    import jax
    import jax.numpy as jnp

    def head(x, rows, gain, w_head):
        h = _zrms(x[rows], gain, cfg["rms_norm_eps"])
        v = w_head.shape[0]
        step = _whole(v, VOCAB_ROWS, "vocabulary")
        part = jax.lax.map(
            lambda at: mm(h, jax.lax.dynamic_slice_in_dim(
                w_head, at, step).T), jnp.arange(0, v, step))
        return part.transpose(1, 0, 2).reshape(len(rows), v)

    return head


def reference_rows(cfg: dict, layers: int, weights: dict, ids, rows,
                   precision: str = "f32"):
    """Logits [len(rows), vocab] float32 of the plain decoder over `ids`
    [S] at positions `rows`: float32 jax.numpy at "highest", one program
    a sublayer so that the float32 copies of the weights exist a few
    matrices at a time, beside the bfloat16 weights made again from the
    seed. `precision` "fp8": every product's operands rounded to
    fp8-e4m3 (the control)."""
    import jax
    import jax.numpy as jnp

    mm = _mm(precision)
    kinds = layer_kinds(cfg, layers)
    attn = {LINEAR: jax.jit(_linear(cfg, mm)), FULL: jax.jit(_mla(cfg, mm))}
    ffn = {kind: jax.jit(_ffn(cfg, mm, kind)) for kind in (DENSE, EXPERTS)}
    cos, sin = (jnp.asarray(t) for t in _rope_tables(cfg, len(ids)))
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: e[t].astype(jnp.float32))(
            weights["model.embed_tokens.weight"], jnp.asarray(ids, jnp.int32))
        for i, (a, f) in enumerate(kinds):
            p = f"model.layers.{i}."
            aw, fw = ({k: weights[p + n]
                       for k, n in {**_COMMON, **keys}.items()}
                      for keys in (_ATTN_KEYS[a], _FFN_KEYS[f]))
            x = attn[a](x, aw) if a == LINEAR else attn[a](x, aw, cos, sin)
            x = ffn[f](x, fw)
        return np.asarray(jax.jit(_head(cfg, mm))(
            x, jnp.asarray(rows, jnp.int32), weights["model.norm.weight"],
            weights["lm_head.weight"]))

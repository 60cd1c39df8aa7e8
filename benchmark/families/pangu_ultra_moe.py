"""Family `pangu_ultra_moe`: the latent-attention decoder of
openPangu-Ultra-MoE-718B (`model_type: pangu_ultra_moe`) through the
repo's `PanguUltraMoEForCausalLM`, as ONE chip's share of an
expert-parallel deployment.

    RMS(x; g) = x / sqrt(mean(x^2) + eps) * g
    h      = RMS(x; g_in)
    cq     = RMS(h Wqa; g_qa)
    q      = cq Wqb -> [nh, dn + dr] ;  q = [q_nope | rope(q_rope; theta, p)]
    ckv|kr = h Wkva -> [rkv | dr] ;  c = RMS(ckv; g_kva) ;  kr = rope(kr; theta, p)
    k_j^h  = [c_j Wuk^h | kr_j] ,  v_j^h = c_j Wuv^h     Wkvb = [Wuk^h | Wuv^h] a head
    a      = concat_h softmax_{j<=t}(q^h . k_j^h / sqrt(dn + dr)) v_j^h  Wo
    x1     = x + RMS(a; g_post_attn)
    h2     = RMS(x1; g_pre_ffn)
    l <  first_k_dense_replace:  f = Wd(silu(Wg h2) * (Wu h2))
    else:  sc = sigmoid(h2 Wr) in R^E ;  T = the k largest ;
           g_e = s * sc_e / (sum_T sc + 1e-20)
           f = sum_{e in T, e HELD HERE} g_e Wd_e(silu(Wg_e h2) * (Wu_e h2))
               + Wd'(silu(Wg' h2) * (Wu' h2))
    x'     = x1 + RMS(f; g_post_ffn)
    logits = RMS(x_L; g_f) Wlm^T                    Wlm untied

rope: rotate-half, dimension i of the dr pairs with i + dr / 2. The
EXPANDED form only: every position's latent goes through Wkvb; nothing is
absorbed, nothing cached.

What the benchmark owns of the family: the configuration file -> the
program's model (built under `paddle.LazyGuard()`), the weights' names
and shapes, the operations a token needs, and the plain float32 reference
with its fp8 control. The reference imports nothing of the program and is
given the same share: the router over all E experts, the held experts
computed, the others' part of the sum left out.

Departures from the published description, each the configuration
file's `assumed`: the router (sigmoid, no groups, no bias), the sandwich
norm's reading, the rotary pairing, the softmax scale, one shared expert
of the experts' width, the initialiser, and the multi-token-prediction
module left out.
"""
from __future__ import annotations

import numpy as np

from .cohere2_moe import _mm

DEPTH_KEY = "num_hidden_layers"
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
            "experts": int(ep["experts_total"]),
            "held": int(cfg["n_routed_experts"]),
            "first": int(ep["rank"]) * int(cfg["n_routed_experts"]),
            "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"],
            "scale": float(cfg["routed_scaling_factor"]),
            "eps": cfg["rms_norm_eps"]}


def layer_kinds(cfg: dict, layers: int) -> list:
    return [DENSE if i < cfg["first_k_dense_replace"] else EXPERTS
            for i in range(layers)]


def weight_spec(cfg: dict, layers: int) -> list:
    """[(name, shape, init)] in the order the weights are made; names are
    the program's `named_parameters()` names, matrices are [in, out]. The
    embedding and the head FIRST: `weights.make` draws float32 before it
    casts, and their 4.7 GB each fit only while little else has been
    made. No other leaf is above 0.15 B elements."""
    z = _sizes(cfg)
    h, nh = z["h"], z["nh"]
    spec = [("model.embed_tokens.weight", (z["v"], h), "normal"),
            ("lm_head.weight", (z["v"], h), "normal")]
    for i, kind in enumerate(layer_kinds(cfg, layers)):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        spec += [(p + "input_layernorm.weight", (h,), "ones"),
                 (p + "post_attention_layernorm.weight", (h,), "ones"),
                 (p + "pre_mlp_layernorm.weight", (h,), "ones"),
                 (p + "post_mlp_layernorm.weight", (h,), "ones"),
                 (a + "q_a_proj", (h, z["rq"]), "normal"),
                 (a + "q_a_layernorm", (z["rq"],), "ones"),
                 (a + "q_b_proj", (z["rq"], nh * (z["dn"] + z["dr"])),
                  "normal"),
                 (a + "kv_a_proj_with_mqa", (h, z["rkv"] + z["dr"]),
                  "normal"),
                 (a + "kv_a_layernorm", (z["rkv"],), "ones"),
                 (a + "kv_b_proj", (z["rkv"], nh * (z["dn"] + z["dv"])),
                  "normal"),
                 (a + "o_proj", (nh * z["dv"], h), "normal")]
        if kind == DENSE:
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
    spec.append(("model.norm.weight", (h,), "ones"))
    return spec


def build_model(cfg: dict, layers: int, role: str):
    """The program's model in the configuration's dtype, built under
    `paddle.LazyGuard()`: shapes and no buffers (`serve.py` assigns the
    seed's weights next; an eager float32 initialisation would not fit)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import (PanguUltraMoEConfig,
                                        PanguUltraMoEForCausalLM)

    if role != "serve":
        raise ValueError(f"{cfg['name']} is cut for serving; role {role!r} "
                         "has no depth in its file")
    z = _sizes(cfg)
    mcfg = PanguUltraMoEConfig(
        vocab_size=z["v"], hidden_size=z["h"], intermediate_size=z["f"],
        moe_intermediate_size=z["fe"], num_hidden_layers=layers,
        first_k_dense_replace=min(cfg["first_k_dense_replace"], layers),
        num_attention_heads=z["nh"], q_lora_rank=z["rq"],
        kv_lora_rank=z["rkv"], qk_nope_head_dim=z["dn"],
        qk_rope_head_dim=z["dr"], v_head_dim=z["dv"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=z["eps"], rope_theta=cfg["rope_theta"],
        n_routed_experts=z["experts"], n_shared_experts=z["shared"],
        num_experts_per_tok=z["k"], routed_scaling_factor=z["scale"],
        norm_topk_prob=cfg["norm_topk_prob"],
        sandwich_norm=cfg["sandwich_norm"], num_local_experts=z["held"],
        expert_rank=int(cfg["expert_parallel"]["rank"]),
        tie_word_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"])
    with paddle.LazyGuard():
        model = PanguUltraMoEForCausalLM(mcfg)
    model.eval()
    return model


# ----------------------------------------------------------- operations

def attention_shape(cfg: dict) -> dict:
    """What a decode over the latent cache moves a token: `heads` queries
    of `latent_dim + rope_dim` against ONE cached row."""
    return {"heads": cfg["num_attention_heads"], "kv_heads": 1,
            "head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "v_head_dim": cfg["v_head_dim"],
            "latent_dim": cfg["kv_lora_rank"],
            "rope_dim": cfg["qk_rope_head_dim"]}


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    """The latent and the rotary key of one position: the published
    width, whatever the pool pads it to."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def attn_flops_per_layer(cfg: dict, q_tokens: float, ctx_sum: float) -> float:
    """The published (expanded) form: QK^T over dn + dr and PV over dv,
    2 FLOPs x heads a pair. Neither re-expanding a cached row nor the
    absorbed form's surplus counts."""
    del q_tokens
    z = _sizes(cfg)
    return 2.0 * z["nh"] * (z["dn"] + z["dr"] + z["dv"]) * ctx_sum


def attn_params(cfg: dict) -> int:
    z = _sizes(cfg)
    h, nh = z["h"], z["nh"]
    return (h * z["rq"] + z["rq"] * nh * (z["dn"] + z["dr"])
            + h * (z["rkv"] + z["dr"]) + z["rkv"] * nh * (z["dn"] + z["dv"])
            + nh * z["dv"] * h)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_params(cfg: dict, layers: int) -> float:
    """Parameters a token multiplies in the blocks, the routed term by
    EXPECTATION: of its k picks over E experts, k x held / E land on this
    chip (a quarter of a pick at 8 of 256; the measured share is in
    PERF.md). Wkvb counts once a token, as the published form has it."""
    z = _sizes(cfg)
    kinds = layer_kinds(cfg, layers)
    n_dense = sum(k == DENSE for k in kinds)
    dense = 3 * z["h"] * z["f"]
    sparse = (z["shared"] * expert_params(cfg) + z["h"] * z["experts"]
              + z["k"] * z["held"] / z["experts"] * expert_params(cfg))
    return (layers * attn_params(cfg) + n_dense * dense
            + (layers - n_dense) * sparse)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def serve_flops(cfg: dict, layers: int, tokens: float, logit_rows: float,
                ctx_sum: float) -> float:
    """Forward only: `tokens` through the blocks, `logit_rows` through the
    head, attention over `ctx_sum` attended keys in every layer."""
    return (2.0 * matmul_params(cfg, layers) * tokens
            + 2.0 * head_params(cfg) * logit_rows
            + layers * attn_flops_per_layer(cfg, tokens, ctx_sum))


# -------------------------------------------------------------- reference

def _rope_tables(cfg: dict, s: int):
    """cos, sin [S, dr / 2] float32, angles worked out in float64."""
    dr = cfg["qk_rope_head_dim"]
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, dr, 2, dtype=np.float64)
                                       / dr))
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


_ATTN_KEYS = {"ln_in": "input_layernorm.weight",
              "ln_post_attn": "post_attention_layernorm.weight",
              "q_a": "self_attn.q_a_proj", "q_a_ln": "self_attn.q_a_layernorm",
              "q_b": "self_attn.q_b_proj",
              "kv_a": "self_attn.kv_a_proj_with_mqa",
              "kv_a_ln": "self_attn.kv_a_layernorm",
              "kv_b": "self_attn.kv_b_proj", "o": "self_attn.o_proj"}
_FFN_KEYS = {
    DENSE: {"ln_pre_ffn": "pre_mlp_layernorm.weight",
            "ln_post_ffn": "post_mlp_layernorm.weight",
            "gate": "mlp.gate_proj", "up": "mlp.up_proj",
            "down": "mlp.down_proj"},
    EXPERTS: {"ln_pre_ffn": "pre_mlp_layernorm.weight",
              "ln_post_ffn": "post_mlp_layernorm.weight",
              "router": "mlp.router.weight",
              "gate": "mlp.experts.gate_proj", "up": "mlp.experts.up_proj",
              "down": "mlp.experts.down_proj",
              "sgate": "mlp.shared_experts.gate_proj",
              "sup": "mlp.shared_experts.up_proj",
              "sdown": "mlp.shared_experts.down_proj"}}

#: query rows the reference works on at once, and heads: the float32
#: scores held are [HEADS, ROWS, S] = 268 MB at 16 x 256 x 16384
ROWS = 256
HEADS = 16
#: rows of the head the logits multiply at once (472 MB in float32)
VOCAB_ROWS = 15360


def _whole(n: int, step: int, what: str) -> int:
    step = n if n <= step else step
    if n % step:
        raise ValueError(f"{what} {n} is not whole blocks of {step}")
    return step


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp

    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * g.astype(jnp.float32)


def _rope(a, cos, sin):
    """Rotate-half on the last axis of a [S, ..., dr]: (i, i + dr/2)."""
    import jax.numpy as jnp

    a1, a2 = jnp.split(a, 2, axis=-1)
    shape = (cos.shape[0],) + (1,) * (a.ndim - 2) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([a1 * c - a2 * s, a2 * c + a1 * s], axis=-1)


def _attention(cfg: dict, mm):
    """x [S, H] float32 -> x1 = x + RMS(attention(x)): the latent and the
    rotary key of the whole sequence first, then `HEADS` heads at a time
    (their keys and values expanded from the latent), the query rows
    `ROWS` at a time; each group's part of the output product added up."""
    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    nh, dn, dr, dv, eps = z["nh"], z["dn"], z["dr"], z["dv"], z["eps"]
    rq, rkv = z["rq"], z["rkv"]

    def attention(x, lw, cos, sin):
        s_len = x.shape[0]
        rows = _whole(s_len, ROWS, "sequence")
        g = _whole(nh, HEADS, "head count")
        h = _rms(x, lw["ln_in"], eps)
        cq = _rms(mm(h, lw["q_a"]), lw["q_a_ln"], eps)
        ckv = mm(h, lw["kv_a"])
        c = _rms(ckv[:, :rkv], lw["kv_a_ln"], eps)
        kr = _rope(ckv[:, rkv:], cos, sin)                       # [S, dr]
        wq = lw["q_b"].reshape(rq, nh // g, g * (dn + dr)).transpose(1, 0, 2)
        wkv = lw["kv_b"].reshape(rkv, nh // g, g * (dn + dv)).transpose(
            1, 0, 2)
        wo = lw["o"].reshape(nh // g, g * dv, -1)
        kv_pos = jnp.arange(s_len)

        def group(acc, ws):
            wq_g, wkv_g, wo_g = ws
            q = mm(cq, wq_g).reshape(s_len, g, dn + dr)
            q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cos, sin)
            kv = mm(c, wkv_g).reshape(s_len, g, dn + dv)
            k_nope, v = kv[..., :dn], kv[..., dn:]

            def some_rows(start):
                qn = jax.lax.dynamic_slice_in_dim(q_nope, start, rows)
                qr = jax.lax.dynamic_slice_in_dim(q_rope, start, rows)
                pos = start + jnp.arange(rows)
                sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                      + jnp.einsum("qhd,kd->hqk", qr, kr)) \
                    * (dn + dr) ** -0.5
                sc = jnp.where((pos[:, None] >= kv_pos[None, :])[None],
                               sc, -jnp.inf)
                return jnp.einsum("hqk,khd->qhd",
                                  jax.nn.softmax(sc, axis=-1), v)

            a = jax.lax.map(some_rows, jnp.arange(0, s_len, rows))
            return acc + mm(a.reshape(s_len, g * dv), wo_g), None

        a, _ = jax.lax.scan(group, jnp.zeros_like(x), (wq, wkv, wo))
        return x + _rms(a, lw["ln_post_attn"], eps)

    return attention


def _ffn(cfg: dict, mm, kind: str):
    """x1 [S, H] float32 -> x1 + RMS(ffn(RMS(x1))), `ROWS` rows at a time
    (the held experts one at a time)."""
    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    eps, fe = z["eps"], z["fe"]

    def swiglu(h, wg, wu, wd):
        return mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)

    def ffn(x1, lw):
        s_len = x1.shape[0]
        rows = _whole(s_len, ROWS, "sequence")

        def some_rows(start):
            xr = jax.lax.dynamic_slice_in_dim(x1, start, rows)
            hr = _rms(xr, lw["ln_pre_ffn"], eps)
            if kind == DENSE:
                f = swiglu(hr, lw["gate"], lw["up"], lw["down"])
            else:
                score = jax.nn.sigmoid(mm(hr, lw["router"]))    # [R, E]
                top, idx = jax.lax.top_k(score, z["k"])
                g = z["scale"] * top / (
                    jnp.sum(top, axis=-1, keepdims=True) + 1e-20)

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
            return xr + _rms(f, lw["ln_post_ffn"], eps)

        out = jax.lax.map(some_rows, jnp.arange(0, s_len, rows))
        return out.reshape(s_len, -1)

    return ffn


def _head(cfg: dict, mm):
    import jax
    import jax.numpy as jnp

    def head(x, rows, gain, w_head):
        h = _rms(x[rows], gain, cfg["rms_norm_eps"])
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
    a half layer so that the float32 copies of the weights exist a few
    matrices at a time, beside the bfloat16 weights made again from the
    seed."""
    import jax
    import jax.numpy as jnp

    mm = _mm(precision)
    kinds = layer_kinds(cfg, layers)
    attn = jax.jit(_attention(cfg, mm))
    ffn = {kind: jax.jit(_ffn(cfg, mm, kind)) for kind in set(kinds)}
    cos, sin = (jnp.asarray(t) for t in _rope_tables(cfg, len(ids)))
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: e[t].astype(jnp.float32))(
            weights["model.embed_tokens.weight"], jnp.asarray(ids, jnp.int32))
        for i, kind in enumerate(kinds):
            p = f"model.layers.{i}."
            x = attn(x, {k: weights[p + n] for k, n in _ATTN_KEYS.items()},
                     cos, sin)
            x = ffn[kind](x, {k: weights[p + n]
                              for k, n in _FFN_KEYS[kind].items()})
        return np.asarray(jax.jit(_head(cfg, mm))(
            x, jnp.asarray(rows, jnp.int32), weights["model.norm.weight"],
            weights["lm_head.weight"]))

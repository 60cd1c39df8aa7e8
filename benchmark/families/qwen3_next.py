"""Family `qwen3_next`: the hybrid decoder of Qwen3-Next-80B-A3B-Instruct
(`model_type: qwen3_next`) through the repo's `Qwen3NextForCausalLM`, as
ONE chip's share of an expert-parallel deployment.

    ZRMS(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)      every plain norm zero-centred
    pre-norm:  x1 = x + mixer(ZRMS(x; w_in)) ;  x' = x1 + moe(ZRMS(x1; w_post))
    linear layer (Gated DeltaNet), h = ZRMS(x; w_in):
      per key head g: [q_g dk | k_g dk | v r x dv | z r x dv] = h W_qkvz ;  [b r | a r] = h W_ba
                                                         r = nv / nk; value head g r + i is key head g's i-th
      q,k,v = silu(sum_j w_j x_{t-K+1+j})                causal depthwise conv over [q | k | v], zeros before 0
      q = l2norm(q) / sqrt(dk) ;  k = l2norm(k)          value head i reads key head i // r
      beta = sigmoid(b) ;  g = -exp(A_log) softplus(a + dt_bias)
      S_t = e^g_t S_{t-1} + k_t (beta_t (v_t - e^g_t S_{t-1}^T k_t))^T     S_0 = 0, token by token
      o_t = S_t^T q_t ;  o = RMS(o) w_o silu(z)            a value head; w_o a PLAIN gain
      mixer = o W_out
    full layer (gated attention), per head j: [q_j D | gate_j D] = h W_q ;  k, v = h W_k, h W_v (nkv heads)
      q = ZRMS(q; w_qn), k = ZRMS(k; w_kn) over D ;  rotate-half RoPE on dims [0, dr) only
      a = softmax_{j<=t}(q . k_j / sqrt(D)) v            nh / nkv query heads a KV head
      mixer = (a * sigmoid(gate)) W_o
    moe: p = softmax(h W_r) over ALL E experts ;  T = the k largest ;  g_e = p_e / sum_T p
         moe = sum_{e in T, e HELD HERE} g_e SwiGLU_e(h) + sigmoid(h w_sg) SwiGLU_shared(h)
    logits = ZRMS(x_L; w_f) Wlm^T                        Wlm untied

What the benchmark owns of the family: the configuration file -> the
program's model (built under `paddle.LazyGuard()`), the weights' names
and shapes, the operations a token needs, and the plain float32 reference
with its fp8 control. The reference imports nothing of the program: it
splits the projections index by index as the published code does, runs
the linear layers by their recurrent definition token by token, and is
given the same share of the experts as the program.

Departures from the published description: the configuration file's
`assumed`.
"""
from __future__ import annotations

import numpy as np

from .cohere2_moe import _mm

DEPTH_KEY = "num_hidden_layers"
LINEAR, FULL = "linear", "full"
EXPERTS = "experts"


def depth(cfg: dict, role: str) -> int:
    return int(cfg["num_hidden_layers"][role])


def _sizes(cfg: dict) -> dict:
    ep = cfg["expert_parallel"]
    return {"h": cfg["hidden_size"], "fe": cfg["moe_intermediate_size"],
            "fs": cfg["shared_expert_intermediate_size"],
            "v": cfg["vocab_size"], "nh": cfg["num_attention_heads"],
            "nkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "dr": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            "nk": cfg["linear_num_key_heads"],
            "nv": cfg["linear_num_value_heads"],
            "lk": cfg["linear_key_head_dim"],
            "lv": cfg["linear_value_head_dim"],
            "conv": cfg["linear_conv_kernel_dim"],
            "experts": int(ep["experts_total"]),
            "held": int(cfg["num_experts"]),
            "first": int(ep["rank"]) * int(cfg["num_experts"]),
            "k": cfg["num_experts_per_tok"], "eps": cfg["rms_norm_eps"]}


def layer_kinds(cfg: dict, layers: int) -> list:
    """[(attention, FFN)] a layer: layer i is full where i + 1 is a
    multiple of `full_attention_interval`; every layer has experts
    (`decoder_sparse_step` 1, no `mlp_only_layers`)."""
    every = cfg["full_attention_interval"]
    return [(LINEAR if (i + 1) % every else FULL, EXPERTS)
            for i in range(layers)]


def _conv_dim(z: dict) -> int:
    return 2 * z["nk"] * z["lk"] + z["nv"] * z["lv"]


def weight_spec(cfg: dict, layers: int) -> list:
    """[(name, shape, init)] in the order the weights are made; names are
    the program's `named_parameters()` names, matrices are [in, out]. The
    embedding and the head FIRST (their float32 draws fit only while
    little else has been made). Zero-centred gains are w of ZRMS's (1 +
    w), made normal(0, 0.02) so that the program has to read them; the
    output norm's plain gain and `dt_bias` ones, `A_log` zeros (A = 1:
    `assumed.initializer`)."""
    z = _sizes(cfg)
    h, nh, nkv, d, nv = z["h"], z["nh"], z["nkv"], z["d"], z["nv"]
    spec = [("model.embed_tokens.weight", (z["v"], h), "normal"),
            ("lm_head.weight", (z["v"], h), "normal")]
    for i, (attn, _) in enumerate(layer_kinds(cfg, layers)):
        p = f"model.layers.{i}."
        spec += [(p + "input_layernorm.weight", (h,), "normal"),
                 (p + "post_attention_layernorm.weight", (h,), "normal")]
        if attn == LINEAR:
            a = p + "linear_attn."
            spec += [(a + "in_proj_qkvz",
                      (h, 2 * z["nk"] * z["lk"] + 2 * nv * z["lv"]),
                      "normal"),
                     (a + "in_proj_ba", (h, 2 * nv), "normal"),
                     (a + "conv1d", (z["conv"], _conv_dim(z)), "normal"),
                     (a + "A_log", (nv,), "zeros"),
                     (a + "dt_bias", (nv,), "ones"),
                     (a + "norm", (z["lv"],), "ones"),
                     (a + "out_proj", (nv * z["lv"], h), "normal")]
        else:
            a = p + "self_attn."
            spec += [(a + "q_proj", (h, nh * 2 * d), "normal"),
                     (a + "k_proj", (h, nkv * d), "normal"),
                     (a + "v_proj", (h, nkv * d), "normal"),
                     (a + "o_proj", (nh * d, h), "normal"),
                     (a + "q_norm", (d,), "normal"),
                     (a + "k_norm", (d,), "normal")]
        f, fs, n = z["fe"], z["fs"], z["held"]
        spec += [(p + "mlp.gate.weight", (h, z["experts"]), "normal"),
                 (p + "mlp.experts.gate_proj", (n, h, f), "normal"),
                 (p + "mlp.experts.up_proj", (n, h, f), "normal"),
                 (p + "mlp.experts.down_proj", (n, f, h), "normal"),
                 (p + "mlp.shared_expert.gate_proj", (h, fs), "normal"),
                 (p + "mlp.shared_expert.up_proj", (h, fs), "normal"),
                 (p + "mlp.shared_expert.down_proj", (fs, h), "normal"),
                 (p + "mlp.shared_expert_gate.weight", (h, 1), "normal")]
    spec.append(("model.norm.weight", (h,), "normal"))
    return spec


def build_model(cfg: dict, layers: int, role: str):
    """The program's model in the configuration's dtype, built under
    `paddle.LazyGuard()`: shapes and no buffers (`serve.py` assigns the
    seed's weights next)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import Qwen3NextConfig, Qwen3NextForCausalLM

    if role != "serve":
        raise ValueError(f"{cfg['name']} is cut for serving; role {role!r} "
                         "has no depth in its file")
    z = _sizes(cfg)
    mcfg = Qwen3NextConfig(
        vocab_size=z["v"], hidden_size=z["h"],
        moe_intermediate_size=z["fe"],
        shared_expert_intermediate_size=z["fs"], num_hidden_layers=layers,
        full_attention_interval=cfg["full_attention_interval"],
        num_attention_heads=z["nh"], num_key_value_heads=z["nkv"],
        head_dim=z["d"], partial_rotary_factor=cfg["partial_rotary_factor"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=z["eps"], rope_theta=cfg["rope_theta"],
        num_experts=z["experts"], num_experts_per_tok=z["k"],
        norm_topk_prob=cfg["norm_topk_prob"],
        decoder_sparse_step=cfg["decoder_sparse_step"],
        mlp_only_layers=tuple(cfg["mlp_only_layers"]),
        linear_num_key_heads=z["nk"], linear_num_value_heads=z["nv"],
        linear_key_head_dim=z["lk"], linear_value_head_dim=z["lv"],
        linear_conv_kernel_dim=z["conv"], num_local_experts=z["held"],
        expert_rank=int(cfg["expert_parallel"]["rank"]),
        tie_word_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"])
    with paddle.LazyGuard():
        model = Qwen3NextForCausalLM(mcfg)
    model.eval()
    return model


# ----------------------------------------------------------- operations

def attention_shape(cfg: dict) -> dict:
    """What a decode over the full layers' K/V pages moves a token."""
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "v_head_dim": cfg["head_dim"]}


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    """A full layer's K and V of one position."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


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
    """A full layer: QK^T and PV over D, 2 FLOPs x query heads a pair."""
    del q_tokens
    return 2.0 * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * ctx_sum


def linear_params(cfg: dict) -> int:
    """A linear layer's products a token: W_qkvz, W_ba, the depthwise
    conv (K a channel) and W_out."""
    z = _sizes(cfg)
    h, nv = z["h"], z["nv"]
    return (h * (2 * z["nk"] * z["lk"] + 2 * nv * z["lv"]) + h * 2 * nv
            + z["conv"] * _conv_dim(z) + nv * z["lv"] * h)


def full_params(cfg: dict) -> int:
    """A full layer's products a token: W_q (query and gate), W_k, W_v,
    W_o."""
    z = _sizes(cfg)
    h, nh, d = z["h"], z["nh"], z["d"]
    return h * nh * 2 * d + 2 * h * z["nkv"] * d + nh * d * h


def matmul_params(cfg: dict, layers: int) -> float:
    """Parameters a token multiplies in the blocks, the routed term by
    EXPECTATION: of its k picks over E experts, k x held / E land on this
    chip; the router, the shared expert and its gate every token."""
    z = _sizes(cfg)
    expert = 3 * z["h"] * z["fe"]
    sparse = (3 * z["h"] * z["fs"] + z["h"] + z["h"] * z["experts"]
              + z["k"] * z["held"] / z["experts"] * expert)
    return sum((linear_params(cfg) if attn == LINEAR else full_params(cfg))
               + sparse for attn, _ in layer_kinds(cfg, layers))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def serve_flops(cfg: dict, layers: int, tokens: float, logit_rows: float,
                ctx_sum: float) -> float:
    """Forward only: `tokens` through the blocks and each linear layer's
    recurrence, `logit_rows` through the head, attention over `ctx_sum`
    attended keys in every full layer."""
    kinds = layer_kinds(cfg, layers)
    n_full = sum(a == FULL for a, _ in kinds)
    return (2.0 * matmul_params(cfg, layers) * tokens
            + 2.0 * head_params(cfg) * logit_rows
            + n_full * attn_flops_per_layer(cfg, tokens, ctx_sum)
            + (layers - n_full) * state_flops_per_token_layer(cfg) * tokens)


# -------------------------------------------------------------- reference

_ATTN_KEYS = {
    LINEAR: {"qkvz": "linear_attn.in_proj_qkvz",
             "ba": "linear_attn.in_proj_ba", "conv": "linear_attn.conv1d",
             "A_log": "linear_attn.A_log", "dt_bias": "linear_attn.dt_bias",
             "o_norm": "linear_attn.norm", "out": "linear_attn.out_proj"},
    FULL: {"q": "self_attn.q_proj", "k": "self_attn.k_proj",
           "v": "self_attn.v_proj", "o": "self_attn.o_proj",
           "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm"}}
_FFN_KEYS = {"router": "mlp.gate.weight", "gate": "mlp.experts.gate_proj",
             "up": "mlp.experts.up_proj", "down": "mlp.experts.down_proj",
             "sgate": "mlp.shared_expert.gate_proj",
             "sup": "mlp.shared_expert.up_proj",
             "sdown": "mlp.shared_expert.down_proj",
             "sg": "mlp.shared_expert_gate.weight"}

#: query rows of the attention the reference works on at once: the
#: float32 scores held are [nh / nkv, ROWS, S] = 403 MB at 8 x 128 x 98304
ROWS = 128
#: tokens of a linear layer whose projections are held at once
ROWS_LINEAR = 2048
#: rows of the expert layer at once (each held expert on all of them)
FFN_ROWS = 2048
#: the sequence the reference runs is cut after the last row asked for,
#: rounded up to whole blocks of this (causal: nothing after it is read)
SEQ_BLOCK = 4096
#: rows of the head the logits multiply at once
VOCAB_ROWS = 18992


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


def fix_query_key_value_ordering(qkvz, ba, cfg: dict):
    """(q [S, nk, dk], k, v [S, nv, dv], z, b [S, nv], a) from the
    projections as the published checkpoint lays them out, index by
    index: key head g's block of W_qkvz is [q | k | v of its r value
    heads | z of them], of W_ba [b of them | a of them]."""
    z = _sizes(cfg)
    nk, nv, dk, dv = z["nk"], z["nv"], z["lk"], z["lv"]
    r, s_len = nv // nk, qkvz.shape[0]
    g = qkvz.reshape(s_len, nk, 2 * dk + 2 * r * dv)
    q, k = g[..., :dk], g[..., dk:2 * dk]
    v = g[..., 2 * dk:2 * dk + r * dv].reshape(s_len, nv, dv)
    zg = g[..., 2 * dk + r * dv:].reshape(s_len, nv, dv)
    gba = ba.reshape(s_len, nk, 2 * r)
    return (q, k, v, zg, gba[..., :r].reshape(s_len, nv),
            gba[..., r:].reshape(s_len, nv))


def _linear(cfg: dict, mm):
    """x [S, H] float32 -> x1: the Gated DeltaNet sublayer, its
    recurrence token by token from a zero state; the projections, the
    conv and the output `ROWS_LINEAR` tokens at a time (the recurrent
    state and the conv's last K - 1 inputs carried from block to block),
    so that no [S, nv x dv] tensor exists at a long context."""
    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    nk, nv, dk, dv, eps = z["nk"], z["nv"], z["lk"], z["lv"], z["eps"]
    kk = z["conv"]
    head_of = np.arange(nv) // (nv // nk)              # value head -> key head

    def l2norm(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    def token(st, xs_t):
        q_t, k_t, v_t, b_t, g_t = xs_t
        decayed = jnp.exp(g_t)[:, None, None] * st
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", decayed, k_t))
        st = decayed + jnp.einsum("hk,hv->hkv", k_t, u)
        return st, jnp.einsum("hkv,hk->hv", st, q_t)

    def linear(x, lw):
        s_len, hidden = x.shape
        rows = _whole(s_len, ROWS_LINEAR, "sequence")
        w = lw["conv"].astype(jnp.float32)

        def some_rows(carry, xr):
            st, tail = carry
            h = _zrms(xr, lw["ln_in"], eps)
            q, k, v, zg, b, a = fix_query_key_value_ordering(
                mm(h, lw["qkvz"]), mm(h, lw["ba"]), cfg)
            xs = jnp.concatenate([q.reshape(rows, -1), k.reshape(rows, -1),
                                  v.reshape(rows, -1)], axis=-1)
            xp = jnp.concatenate([tail, xs])        # the K - 1 before, then these
            mixed = jax.nn.silu(sum(w[j] * xp[j:j + rows]
                                    for j in range(kk)))
            q = l2norm(mixed[:, :nk * dk].reshape(rows, nk, dk)) * dk ** -0.5
            k = l2norm(mixed[:, nk * dk:2 * nk * dk].reshape(rows, nk, dk))
            v = mixed[:, 2 * nk * dk:].reshape(rows, nv, dv)
            beta = jax.nn.sigmoid(b)
            g = -jnp.exp(lw["A_log"].astype(jnp.float32)) * jax.nn.softplus(
                a + lw["dt_bias"].astype(jnp.float32))
            st, o = jax.lax.scan(token, st, (q[:, head_of], k[:, head_of], v,
                                             beta, g))
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
            o = o * lw["o_norm"].astype(jnp.float32) * jax.nn.silu(zg)
            out = xr + mm(o.reshape(rows, nv * dv), lw["out"])
            return (st, xp[rows:]), out

        init = (jnp.zeros((nv, dk, dv), jnp.float32),
                jnp.zeros((kk - 1, 2 * nk * dk + nv * dv), jnp.float32))
        _, out = jax.lax.scan(some_rows, init,
                              x.reshape(s_len // rows, rows, hidden))
        return out.reshape(s_len, hidden)

    return linear


def _rope_half(a, cos, sin):
    """Pairs (i, i + dr / 2) of the first dr = 2 cos.shape[-1] dims of a
    [S, heads, D] rotated; the rest as they are."""
    import jax.numpy as jnp

    half = cos.shape[-1]
    c, s = cos[:, None, :], sin[:, None, :]
    x1, x2, rest = a[..., :half], a[..., half:2 * half], a[..., 2 * half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def _rope_tables(cfg: dict, s: int):
    """cos, sin [S, dr / 2] float32 of pair i = dims (i, i + dr / 2),
    angles worked out in float64."""
    dr = _sizes(cfg)["dr"]
    inv = 1.0 / (float(cfg["rope_theta"])
                 ** (np.arange(0, dr, 2, dtype=np.float64) / dr))
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _attention(cfg: dict, mm):
    """x [S, H] float32 -> x1: the gated attention of the whole sequence,
    the query rows `ROWS` at a time (their queries, gates and output
    projection with them) and within them a KV head's group of query
    heads at a time, against every key (the scores whole, masked)."""
    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    nh, nkv, d, eps = z["nh"], z["nkv"], z["d"], z["eps"]
    rep = nh // nkv

    def attention(x, lw, cos, sin):
        s_len = x.shape[0]
        rows = _whole(s_len, ROWS, "sequence")
        h = _zrms(x, lw["ln_in"], eps)
        k = _rope_half(_zrms(mm(h, lw["k"]).reshape(s_len, nkv, d),
                             lw["k_norm"], eps), cos, sin)
        v = mm(h, lw["v"]).reshape(s_len, nkv, d)
        kv_pos = jnp.arange(s_len)

        def some_rows(start):
            hr = jax.lax.dynamic_slice_in_dim(h, start, rows)
            qg = mm(hr, lw["q"]).reshape(rows, nh, 2 * d)
            q = _rope_half(_zrms(qg[..., :d], lw["q_norm"], eps),
                           jax.lax.dynamic_slice_in_dim(cos, start, rows),
                           jax.lax.dynamic_slice_in_dim(sin, start, rows))
            pos = start + jnp.arange(rows)

            def group(j):
                qj = jax.lax.dynamic_slice_in_dim(q, j * rep, rep, axis=1)
                sc = jnp.einsum("qhd,kd->hqk", qj, k[:, j]) * d ** -0.5
                sc = jnp.where((pos[:, None] >= kv_pos[None, :])[None],
                               sc, -jnp.inf)
                return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(sc, -1),
                                  v[:, j])

            a = jax.lax.map(group, jnp.arange(nkv))      # [nkv, R, rep, D]
            a = a.transpose(1, 0, 2, 3).reshape(rows, nh, d)
            a = a * jax.nn.sigmoid(qg[..., d:])
            return jax.lax.dynamic_slice_in_dim(x, start, rows) + mm(
                a.reshape(rows, nh * d), lw["o"])

        out = jax.lax.map(some_rows, jnp.arange(0, s_len, rows))
        return out.reshape(s_len, -1)

    return attention


def _ffn(cfg: dict, mm):
    """x1 [S, H] float32 -> x1 + moe(ZRMS(x1)), `FFN_ROWS` rows at a time
    (the held experts one at a time on all of them)."""
    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    eps = z["eps"]

    def swiglu(h, wg, wu, wd):
        return mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)

    def ffn(x1, lw):
        s_len = x1.shape[0]
        rows = _whole(s_len, FFN_ROWS, "sequence")

        def some_rows(start):
            xr = jax.lax.dynamic_slice_in_dim(x1, start, rows)
            hr = _zrms(xr, lw["ln_post"], eps)
            p = jax.nn.softmax(mm(hr, lw["router"]), axis=-1)    # [R, E]
            top, idx = jax.lax.top_k(p, z["k"])
            g = top / jnp.sum(top, axis=-1, keepdims=True)

            def expert(acc, xs):
                e, wg, wu, wd = xs
                ge = jnp.sum(jnp.where(idx == z["first"] + e, g, 0.0),
                             axis=-1)                           # [R]
                return acc + ge[:, None] * swiglu(hr, wg, wu, wd), None

            f, _ = jax.lax.scan(
                expert, jnp.zeros_like(xr),
                (jnp.arange(z["held"]), lw["gate"], lw["up"], lw["down"]))
            shared = jax.nn.sigmoid(mm(hr, lw["sg"])) * swiglu(
                hr, lw["sgate"], lw["sup"], lw["sdown"])
            return xr + f + shared

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
    a sublayer, beside the bfloat16 weights made again from the seed. The
    sequence is cut after the last row, to whole `SEQ_BLOCK`s. `precision`
    "fp8": every product's operands rounded to fp8-e4m3 (the control)."""
    import jax
    import jax.numpy as jnp

    rows = np.asarray(rows)
    n = min(len(ids), -(-(int(rows.max()) + 1) // SEQ_BLOCK) * SEQ_BLOCK)
    ids = np.asarray(ids)[:n]
    mm = _mm(precision)
    kinds = layer_kinds(cfg, layers)
    attn = {LINEAR: jax.jit(_linear(cfg, mm)),
            FULL: jax.jit(_attention(cfg, mm))}
    ffn = jax.jit(_ffn(cfg, mm))
    cos, sin = (jnp.asarray(t) for t in _rope_tables(cfg, n))
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: e[t].astype(jnp.float32))(
            weights["model.embed_tokens.weight"], jnp.asarray(ids, jnp.int32))
        for i, (a, _) in enumerate(kinds):
            p = f"model.layers.{i}."
            aw = {k: weights[p + name] for k, name in _ATTN_KEYS[a].items()}
            aw["ln_in"] = weights[p + "input_layernorm.weight"]
            fw = {k: weights[p + name] for k, name in _FFN_KEYS.items()}
            fw["ln_post"] = weights[p + "post_attention_layernorm.weight"]
            x = attn[a](x, aw) if a == LINEAR else attn[a](x, aw, cos, sin)
            x = ffn(x, fw)
        return np.asarray(jax.jit(_head(cfg, mm))(
            x, jnp.asarray(rows, jnp.int32), weights["model.norm.weight"],
            weights["lm_head.weight"]))

"""Family `cohere2_moe`: the parallel-block decoder of Command A+
(`model_type: cohere2_moe`) through the repo's `Cohere2MoeForCausalLM`, as
ONE chip's share of an expert-parallel deployment.

    h      = LN(x)                     (x - mean) / sqrt(var + eps) * g, no bias
    q,k,v  = h Wq, h Wk, h Wv          heads of `head_dim`, no bias, no QK-norm
    sliding_attention:  q,k rotated in interleaved pairs (2i, 2i+1) at theta;
                        key j visible from query t  iff  0 <= t - j < W
    full_attention:     no positional encoding;  key j visible iff j <= t
    a      = softmax(q k^T / sqrt(hd) + mask) v Wo
    s      = sigmoid(h Wr) in R^E ;  T = the k largest ;  g_e = s_e / sum_T s
    routed = sum_{e in T, e HELD HERE} g_e Wd_e(silu(Wg_e h) * (Wu_e h))
    shared = (1/S) sum_j Wd'_j(silu(Wg'_j h) * (Wu'_j h))
    x'     = x + a + routed + shared
    logits = logit_scale * LN_f(x_L) E^T          E the embedding (tied)

What the benchmark owns of the family: the configuration file -> the
program's model (built under `paddle.LazyGuard()`: no buffer before the
seed's weights are assigned), the weights' names and shapes, the
operations a token needs, and the plain float32 reference with its fp8
control. The reference imports nothing of the program and is given the
same share: the router over all E experts, the held experts computed,
the others' part of the sum left out.
"""
from __future__ import annotations

import numpy as np

from .llama import _round_bits

DEPTH_KEY = "num_hidden_layers"
SLIDING = "sliding_attention"


def depth(cfg: dict, role: str) -> int:
    return int(cfg["num_hidden_layers"][role])


def _sizes(cfg: dict) -> dict:
    ep = cfg["expert_parallel"]
    return {"h": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "hd": cfg["head_dim"],
            "nh": cfg["num_attention_heads"],
            "nkv": cfg["num_key_value_heads"],
            "experts": int(ep["experts_total"]),
            "held": int(cfg["num_experts"]),
            "first": int(ep["rank"]) * int(cfg["num_experts"]),
            "k": cfg["num_experts_per_tok"],
            "shared": cfg["num_shared_experts"],
            "window": cfg["sliding_window"]}


def weight_spec(cfg: dict, layers: int) -> list:
    """[(name, shape, init)] in the order the weights are made; names are
    the program's `named_parameters()` names, matrices are [in, out]. The
    embedding FIRST: `weights.make` draws float32 before it casts, and its
    4.3 GB fit only while little else has been made. No other leaf is
    above 0.27 B elements (the routed experts are three stacks)."""
    z = _sizes(cfg)
    h, f, q, kv = z["h"], z["f"], z["nh"] * z["hd"], z["nkv"] * z["hd"]
    n, s = z["held"], z["shared"]
    spec = [("model.embed_tokens.weight", (z["v"], h), "normal")]
    for i in range(layers):
        p = f"model.layers.{i}."
        spec += [(p + "input_layernorm.weight", (h,), "ones"),
                 (p + "self_attn.q_proj", (h, q), "normal"),
                 (p + "self_attn.k_proj", (h, kv), "normal"),
                 (p + "self_attn.v_proj", (h, kv), "normal"),
                 (p + "self_attn.o_proj", (q, h), "normal"),
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
    from paddle_tpu.text.models import (Cohere2MoeConfig,
                                        Cohere2MoeForCausalLM)

    if role != "serve":
        raise ValueError(f"{cfg['name']} is cut for serving; role {role!r} "
                         "has no depth in its file")
    z = _sizes(cfg)
    mcfg = Cohere2MoeConfig(
        vocab_size=z["v"], hidden_size=z["h"], intermediate_size=z["f"],
        num_hidden_layers=layers, num_attention_heads=z["nh"],
        num_key_value_heads=z["nkv"], head_dim=z["hd"],
        max_position_embeddings=cfg["max_position_embeddings"],
        layer_norm_eps=cfg["layer_norm_eps"], rope_theta=cfg["rope_theta"],
        sliding_window=z["window"],
        layer_types=tuple(cfg["layer_types"][:layers]),
        num_experts=z["experts"], num_experts_per_tok=z["k"],
        num_shared_experts=z["shared"], num_local_experts=z["held"],
        expert_rank=int(cfg["expert_parallel"]["rank"]),
        logit_scale=cfg["logit_scale"],
        tie_word_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"])
    with paddle.LazyGuard():
        model = Cohere2MoeForCausalLM(mcfg)
    model.eval()
    return model


# ----------------------------------------------------------- operations

def attention_shape(cfg: dict) -> dict:
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"]}


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def layer_kinds(cfg: dict, layers: int) -> list:
    return list(cfg["layer_types"][:layers])


def attn_flops_per_layer(cfg: dict, q_tokens: float, ctx_sum: float) -> float:
    """QK^T and PV over `ctx_sum` attended keys: 2 matmuls x 2 FLOPs x
    heads x head_dim."""
    del q_tokens
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * ctx_sum


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def matmul_params(cfg: dict, layers: int) -> float:
    """Parameters a token multiplies in the blocks, the routed term by
    EXPECTATION: of its k picks over E experts, k x held / E land on this
    chip (12.5% of them at 16 of 128; the measured share is in PERF.md)."""
    z = _sizes(cfg)
    q, kv = z["nh"] * z["hd"], z["nkv"] * z["hd"]
    attn = z["h"] * q + 2 * z["h"] * kv + q * z["h"]
    shared = z["shared"] * expert_params(cfg)
    router = z["h"] * z["experts"]
    routed = z["k"] * z["held"] / z["experts"] * expert_params(cfg)
    return layers * (attn + shared + router + routed)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def serve_flops(cfg: dict, layers: int, tokens: float, logit_rows: float,
                ctx_sum: float) -> float:
    """Forward only: `tokens` through the blocks, `logit_rows` through the
    head, attention over `ctx_sum` attended keys on the full layers and
    over `min(ctx_sum, tokens x W)` on the sliding ones: exact for decode
    where every context is at least the window, over for a prompt's
    prefill by W(W-1)/2 key visits a sliding layer (PERF.md sizes it)."""
    kinds = layer_kinds(cfg, layers)
    n_win = sum(k == SLIDING for k in kinds)
    win_ctx = min(ctx_sum, tokens * cfg["sliding_window"])
    return (2.0 * matmul_params(cfg, layers) * tokens
            + 2.0 * head_params(cfg) * logit_rows
            + (layers - n_win) * attn_flops_per_layer(cfg, tokens, ctx_sum)
            + n_win * attn_flops_per_layer(cfg, tokens, win_ctx))


# -------------------------------------------------------------- reference

def _mm(precision: str):
    """a @ w in float32 at "highest" — or, for the control, with both
    operands rounded to fp8-e4m3."""
    import jax.numpy as jnp

    f32 = jnp.float32
    if precision == "f32":
        return lambda a, w: a @ w.astype(f32)
    if precision == "fp8":
        return lambda a, w: _round_bits(a, 4) @ _round_bits(w.astype(f32), 4)
    raise ValueError(precision)


def _rope_tables(cfg: dict, s: int):
    """cos, sin [S, head_dim / 2] float32, angles worked out in float64."""
    hd = cfg["head_dim"]
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, hd, 2, dtype=np.float64)
                                       / hd))
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


_LAYER_KEYS = {"ln": "input_layernorm.weight", "q": "self_attn.q_proj",
               "k": "self_attn.k_proj", "v": "self_attn.v_proj",
               "o": "self_attn.o_proj", "router": "mlp.router.weight",
               "gate": "mlp.experts.gate_proj", "up": "mlp.experts.up_proj",
               "down": "mlp.experts.down_proj",
               "sgate": "mlp.shared_experts.gate_proj",
               "sup": "mlp.shared_experts.up_proj",
               "sdown": "mlp.shared_experts.down_proj"}

#: query rows the reference works on at once: the float32 scores held are
#: [heads a KV head, ROWS, S] = 537 MB at 16 x 512 x 16384
ROWS = 512
#: rows of the embedding the head multiplies at once (537 MB in float32)
VOCAB_ROWS = 32768


def _layer_norm(x, g, eps):
    import jax
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _block(cfg: dict, mm, kind: str):
    """x [S, H] float32 -> x': one layer, the keys and values of the whole
    sequence first, then the query rows `ROWS` at a time (attention one KV
    head's group at a time, the held experts one at a time)."""
    import jax
    import jax.numpy as jnp

    z = _sizes(cfg)
    nh, nkv, hd, f = z["nh"], z["nkv"], z["hd"], z["f"]
    rep, eps = nh // nkv, cfg["layer_norm_eps"]
    sliding = kind == SLIDING

    def rope(a, cos, sin):
        """Interleaved: the pair (2i, 2i+1) turns by angle i."""
        a0, a1 = a[..., 0::2], a[..., 1::2]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.stack([a0 * c - a1 * s, a1 * c + a0 * s],
                         axis=-1).reshape(a.shape)

    def block(x, lw, cos, sin):
        s_len = x.shape[0]
        rows = s_len if s_len <= ROWS else ROWS
        if s_len % rows:
            raise ValueError(f"sequence {s_len} is not whole blocks of "
                             f"{rows} rows")
        h = _layer_norm(x, lw["ln"], eps)
        k = mm(h, lw["k"]).reshape(s_len, nkv, hd)
        v = mm(h, lw["v"]).reshape(s_len, nkv, hd)
        if sliding:
            k = rope(k, cos, sin)
        kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)  # [nkv, S, hd]
        kv_pos = jnp.arange(s_len)

        def some_rows(start):
            xr = jax.lax.dynamic_slice_in_dim(x, start, rows)
            hr = jax.lax.dynamic_slice_in_dim(h, start, rows)
            pos = start + jnp.arange(rows)
            q = mm(hr, lw["q"]).reshape(rows, nh, hd)
            if sliding:
                q = rope(q, jax.lax.dynamic_slice_in_dim(cos, start, rows),
                         jax.lax.dynamic_slice_in_dim(sin, start, rows))
            d = pos[:, None] - kv_pos[None, :]
            seen = d >= 0
            if sliding:
                seen = seen & (d < z["window"])

            def group(args):
                qh, kh, vh = args                # [rep,R,hd] [S,hd] [S,hd]
                sc = jnp.einsum("rqd,kd->rqk", qh, kh) * hd ** -0.5
                sc = jnp.where(seen[None], sc, -jnp.inf)
                return jnp.einsum("rqk,kd->rqd",
                                  jax.nn.softmax(sc, axis=-1), vh)

            qg = q.reshape(rows, nkv, rep, hd).transpose(1, 2, 0, 3)
            a = jax.lax.map(group, (qg, kg, vg))            # [nkv,rep,R,hd]
            a = mm(a.transpose(2, 0, 1, 3).reshape(rows, nh * hd), lw["o"])

            score = jax.nn.sigmoid(mm(hr, lw["router"]))    # [R, E]
            top, idx = jax.lax.top_k(score, z["k"])
            g = top / jnp.sum(top, axis=-1, keepdims=True)

            def expert(acc, xs):
                e, wg, wu, wd = xs
                ge = jnp.sum(jnp.where(idx == z["first"] + e, g, 0.0),
                             axis=-1)                       # [R]
                y = mm(jax.nn.silu(mm(hr, wg)) * mm(hr, wu), wd)
                return acc + ge[:, None] * y, None

            routed, _ = jax.lax.scan(
                expert, jnp.zeros_like(xr),
                (jnp.arange(z["held"]), lw["gate"], lw["up"], lw["down"]))
            shared = jnp.zeros_like(xr)
            for j in range(z["shared"]):
                cols = slice(j * f, (j + 1) * f)
                shared = shared + mm(
                    jax.nn.silu(mm(hr, lw["sgate"][:, cols]))
                    * mm(hr, lw["sup"][:, cols]), lw["sdown"][cols, :])
            return xr + a + routed + shared / z["shared"]

        out = jax.lax.map(some_rows, jnp.arange(0, s_len, rows))
        return out.reshape(s_len, -1)

    return block


def _head(cfg: dict, mm):
    import jax
    import jax.numpy as jnp

    def head(x, rows, gain, embed):
        h = _layer_norm(x[rows], gain, cfg["layer_norm_eps"])
        v = embed.shape[0]
        step = v if v <= VOCAB_ROWS else VOCAB_ROWS
        if v % step:
            raise ValueError(f"vocabulary {v} is not whole blocks of {step}")
        part = jax.lax.map(
            lambda at: mm(h, jax.lax.dynamic_slice_in_dim(
                embed, at, step).T), jnp.arange(0, v, step))
        return cfg["logit_scale"] * part.transpose(1, 0, 2).reshape(
            len(rows), v)

    return head


def reference_rows(cfg: dict, layers: int, weights: dict, ids, rows,
                   precision: str = "f32"):
    """Logits [len(rows), vocab] float32 of the plain decoder over `ids`
    [S] at positions `rows`: float32 jax.numpy at "highest", one program a
    block so that the float32 copies of the weights exist one matrix at a
    time, beside the bfloat16 weights made again from the seed."""
    import jax
    import jax.numpy as jnp

    mm = _mm(precision)
    kinds = layer_kinds(cfg, layers)
    fwd = {kind: jax.jit(_block(cfg, mm, kind)) for kind in set(kinds)}
    cos, sin = (jnp.asarray(t) for t in _rope_tables(cfg, len(ids)))
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: e[t].astype(jnp.float32))(
            weights["model.embed_tokens.weight"], jnp.asarray(ids, jnp.int32))
        for i, kind in enumerate(kinds):
            lw = {k: weights[f"model.layers.{i}.{n}"]
                  for k, n in _LAYER_KEYS.items()}
            x = fwd[kind](x, lw, cos, sin)
        return np.asarray(jax.jit(_head(cfg, mm))(
            x, jnp.asarray(rows, jnp.int32), weights["model.norm.weight"],
            weights["model.embed_tokens.weight"]))

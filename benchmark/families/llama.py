"""Family `llama`: decoder-only, RMSNorm, rotary GQA attention, SwiGLU —
Mistral-7B-v0.1 through the repo's `LlamaForCausalLM`.

What the benchmark owns of a family, in one file: how a configuration file
becomes the program's model, the weights made on the device from the seed
(the program and the reference are both GIVEN them), the operations a token
needs (for `step_mfu`), and the plain float32 reference with its
lower-precision control. The reference imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

DEPTH_KEY = "num_hidden_layers"


def depth(cfg: dict, role: str) -> int:
    return int(cfg["num_hidden_layers"][role])


def weight_spec(cfg: dict, layers: int) -> list:
    """[(name, shape, init)] in the order the weights are made; names are
    the program's `named_parameters()` names, matrices are [in, out]."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    spec = [("llama.embed_tokens.weight", (v, h), "normal")]
    for i in range(layers):
        p = f"llama.layers.{i}."
        spec += [(p + "self_attn.q_proj.weight", (h, q), "normal"),
                 (p + "self_attn.k_proj.weight", (h, kv), "normal"),
                 (p + "self_attn.v_proj.weight", (h, kv), "normal"),
                 (p + "self_attn.o_proj.weight", (q, h), "normal"),
                 (p + "mlp.gate_proj.weight", (h, f), "normal"),
                 (p + "mlp.up_proj.weight", (h, f), "normal"),
                 (p + "mlp.down_proj.weight", (f, h), "normal"),
                 (p + "input_layernorm.weight", (h,), "ones"),
                 (p + "post_attention_layernorm.weight", (h,), "ones")]
    spec += [("llama.norm.weight", (h,), "ones"),
             ("lm_head.weight", (h, v), "normal")]
    return spec


def build_model(cfg: dict, layers: int, role: str):
    """The program's model for this configuration, in bf16 (amp O2), with
    the program's own initial weights still in it."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaForCausalLM
    from paddle_tpu.text.models.llama import LlamaConfig

    mcfg = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_hidden_layers=layers,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        use_recompute=(role == "train"), recompute_granularity="full")
    assert mcfg.head_dim == cfg["head_dim"]
    model = LlamaForCausalLM(mcfg)
    if role == "serve":
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                    master_weight=False)
        model.eval()
    return model


# ----------------------------------------------------------- operations

def matmul_params(cfg: dict, layers: int) -> int:
    """Parameters a token multiplies in the blocks (no embedding lookup,
    no head)."""
    h, f, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return layers * (h * q + 2 * h * kv + q * h + 3 * h * f)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def attn_flops_per_layer(cfg: dict, q_tokens: float, ctx_sum: float) -> float:
    """QK^T and PV over `ctx_sum` = the sum over query tokens of the keys
    each attends to: 2 matmuls x 2 FLOPs x heads x head_dim."""
    del q_tokens
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * ctx_sum


def train_flops_per_step(cfg: dict, layers: int, batch: int, seq: int) -> float:
    """Forward + backward of one step, recompute not counted: 6 per
    multiplied parameter per token, plus causal attention (each token
    attends to (seq + 1) / 2 keys on average) three times over."""
    tokens = batch * seq
    dense = 6.0 * (matmul_params(cfg, layers) + head_params(cfg)) * tokens
    ctx = batch * seq * (seq + 1) / 2.0
    return dense + 3.0 * layers * attn_flops_per_layer(cfg, tokens, ctx)


def serve_flops(cfg: dict, layers: int, tokens: float, logit_rows: float,
                ctx_sum: float) -> float:
    """Forward only: `tokens` through the blocks, `logit_rows` through the
    head, attention over `ctx_sum` attended keys in all."""
    return (2.0 * matmul_params(cfg, layers) * tokens
            + 2.0 * head_params(cfg) * logit_rows
            + layers * attn_flops_per_layer(cfg, tokens, ctx_sum))


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


# -------------------------------------------------------------- reference

def _round_bits(x, bits: int):
    """Round to `bits` significant bits (float8-e4m3 has 4, e5m2 has 3)
    under a per-tensor scale, by arithmetic alone: what an fp8 matmul
    would be fed."""
    import jax.numpy as jnp

    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 2.0 ** bits) / 2.0 ** bits, e)


def _mm_fp8():
    """a @ w as an fp8 training recipe computes it: both operands in
    e4m3 forward, the incoming gradient in e5m2 for both backward matmuls
    (rounding has no gradient of its own)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def operands(a, w):
        return _round_bits(a, 4), _round_bits(w.astype(f32), 4)

    @jax.custom_vjp
    def mm(a, w):
        qa, qw = operands(a, w)
        return qa @ qw

    def fwd(a, w):
        qa, qw = operands(a, w)
        return qa @ qw, (qa, qw, jnp.zeros((), w.dtype))

    def bwd(res, dy):
        qa, qw, like = res
        dq = _round_bits(dy, 3)
        dw = qa.reshape(-1, qa.shape[-1]).T @ dq.reshape(-1, dq.shape[-1])
        return dq @ qw.T, dw.astype(like.dtype)

    mm.defvjp(fwd, bwd)
    return mm


def _mm(precision: str):
    """a @ w in float32 at "highest" — or, for the control, in fp8."""
    import jax.numpy as jnp

    if precision == "f32":
        return lambda a, w: a @ w.astype(jnp.float32)
    if precision == "fp8":
        return _mm_fp8()
    raise ValueError(precision)


def _rope_tables(cfg: dict, s: int):
    """cos, sin [1, S, 1, head_dim] float32, angles worked out in float64."""
    import jax.numpy as jnp

    hd = cfg["head_dim"]
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, hd, 2, dtype=np.float64)
                                       / hd))
    ang = np.outer(np.arange(s, dtype=np.float64), inv)
    ang = np.concatenate([ang, ang], axis=-1)
    return (jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :],
            jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :])


def _layer_w(params: dict, i: int) -> dict:
    return {k: params[f"llama.layers.{i}.{n}"]
            for k, n in _LAYER_KEYS.items()}


def reference_rows(cfg: dict, layers: int, weights: dict, ids, rows,
                   precision: str = "f32"):
    """Logits [len(rows), vocab] float32 of the plain decoder over
    `ids` [S] at positions `rows`: the reference trainer's block (float32
    jax.numpy, causal softmax attention written out one KV group at a
    time), one program per block so that the float32 copies of the
    weights exist one matrix at a time."""
    import jax
    import jax.numpy as jnp

    mm = _mm(precision)
    block, rms = _block(cfg, mm)
    cos, sin = _rope_tables(cfg, len(ids))
    fwd = jax.jit(block)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda e, t: e.astype(jnp.float32)[t][None])(
            weights["llama.embed_tokens.weight"], jnp.asarray(ids, jnp.int32))
        for i in range(layers):
            x = fwd(x, _layer_w(weights, i), cos, sin)
        head = jax.jit(lambda x, r, g, w: mm(rms(x[0, r], g), w))
        return np.asarray(head(x, jnp.asarray(rows, jnp.int32),
                               weights["llama.norm.weight"],
                               weights["lm_head.weight"]))


# ------------------------------------------------------ reference trainer

def _block(cfg, mm):
    """x [B, S, H] float32 -> x: the decoder block, attention one
    (row, KV group) at a time so the [S, S] scores stay small."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, rep = cfg["rms_norm_eps"], nh // nkv

    def rms(x, g):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * g.astype(f32)

    def block(x, lw, cos, sin):
        b, s, _ = x.shape

        def rope(a):
            a1, a2 = jnp.split(a, 2, axis=-1)
            return a * cos + jnp.concatenate([-a2, a1], axis=-1) * sin

        @jax.checkpoint
        def attend(qkv):
            q, k, v = qkv                         # [rep,S,hd] [S,hd] [S,hd]
            sc = jnp.einsum("rqd,kd->rqk", q, k) * hd ** -0.5
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc,
                           -jnp.inf)
            return jnp.einsum("rqk,kd->rqd", jax.nn.softmax(sc, axis=-1), v)

        h = rms(x, lw["ln1"])
        q = rope(mm(h, lw["q"]).reshape(b, s, nh, hd))
        k = rope(mm(h, lw["k"]).reshape(b, s, nkv, hd))
        v = mm(h, lw["v"]).reshape(b, s, nkv, hd)
        qg = q.reshape(b, s, nkv, rep, hd).transpose(0, 2, 3, 1, 4)
        kg, vg = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        a = jax.lax.map(attend, (qg.reshape(b * nkv, rep, s, hd),
                                 kg.reshape(b * nkv, s, hd),
                                 vg.reshape(b * nkv, s, hd)))
        a = a.reshape(b, nkv, rep, s, hd).transpose(0, 3, 1, 2, 4)
        x = x + mm(a.reshape(b, s, nh * hd), lw["o"])
        h = rms(x, lw["ln2"])
        return x + mm(jax.nn.silu(mm(h, lw["gate"])) * mm(h, lw["up"]),
                      lw["down"])

    return block, rms


_LAYER_KEYS = {"ln1": "input_layernorm.weight",
               "ln2": "post_attention_layernorm.weight",
               "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
               "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
               "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
               "down": "mlp.down_proj.weight"}


def reference_grads(cfg: dict, layers: int, params: dict, ids, on_grad,
                    precision: str = "f32") -> float:
    """One forward and backward of the plain float32 model over `ids`
    [B, S] with labels = ids (the program's `model(x, x)`: position t is
    scored against token t, mean over all). Gradients are handed to
    `on_grad(name, array)` a leaf at a time, top of the model first, so
    that the caller never holds them all. Returns the loss."""
    import jax
    import jax.numpy as jnp

    from ..reference_train import blocked_head, token_losses

    f32 = jnp.float32
    mm = _mm(precision)
    block, rms = _block(cfg, mm)
    cos, sin = _rope_tables(cfg, ids.shape[1])
    ids = jnp.asarray(ids, jnp.int32)

    def head_rows(xr, idr, gw):
        return token_losses(mm(rms(xr, gw[0]), gw[1]), idr)

    fwd = jax.jit(block)
    bwd = jax.jit(lambda x, lw, dx: jax.vjp(
        lambda x_, lw_: block(x_, lw_, cos, sin), x, lw)[1](dx))
    with jax.default_matmul_precision("highest"):
        xs = [jax.jit(lambda e: e[ids])(params["llama.embed_tokens.weight"])]
        for i in range(layers):
            xs.append(fwd(xs[-1], _layer_w(params, i), cos, sin))
        loss, dx, (g_norm, g_head) = blocked_head(
            head_rows, xs.pop(), ids,
            (params["llama.norm.weight"], params["lm_head.weight"]))
        on_grad("lm_head.weight", g_head)
        on_grad("llama.norm.weight", g_norm)
        del g_head, g_norm
        for i in reversed(range(layers)):
            dx, g = bwd(xs.pop(), _layer_w(params, i), dx)
            for k, n in _LAYER_KEYS.items():
                on_grad(f"llama.layers.{i}.{n}", g[k])
            del g
        emb = params["llama.embed_tokens.weight"]
        on_grad("llama.embed_tokens.weight", jax.jit(
            lambda d: jnp.zeros(emb.shape, f32).at[ids].add(d))(dx))
    return loss


def attention_shape(cfg: dict) -> dict:
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"]}

"""Family `gpt`: GPT-2/3 style decoder — learned positions, pre-LayerNorm,
fused QKV, erf-GELU MLP — Cerebras-GPT-1.3B through the repo's
`GPTForCausalLM`. Same contract as families/llama.py; the float32
reference imports nothing of the program.

What the repo's GPT block does differently from the published model is
listed under `assumed` in the configuration file: no biases on the linear
layers, an output head that is not tied to the embedding.
"""
from __future__ import annotations

import numpy as np

from .llama import _mm

DEPTH_KEY = "n_layer"


def depth(cfg: dict, role: str) -> int:
    return int(cfg["n_layer"][role])


def weight_spec(cfg: dict, layers: int) -> list:
    h, f, v, p = (cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"],
                  cfg["n_positions"])
    spec = [("wte.weight", (v, h), "normal"), ("wpe.weight", (p, h), "normal")]
    for i in range(layers):
        b = f"blocks.{i}."
        spec += [(b + "ln_1.weight", (h,), "ones"),
                 (b + "ln_1.bias", (h,), "zeros"),
                 (b + "attn.qkv_proj.weight", (h, 3 * h), "normal"),
                 (b + "attn.out_proj.weight", (h, h), "normal"),
                 (b + "ln_2.weight", (h,), "ones"),
                 (b + "ln_2.bias", (h,), "zeros"),
                 (b + "fc_in.weight", (h, f), "normal"),
                 (b + "fc_out.weight", (f, h), "normal")]
    spec += [("ln_f.weight", (h,), "ones"), ("ln_f.bias", (h,), "zeros"),
             ("lm_head.weight", (h, v), "normal")]
    return spec


def build_model(cfg: dict, layers: int, role: str):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import GPTConfig

    model = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_hidden_layers=layers, num_attention_heads=cfg["n_head"],
        intermediate_size=cfg["n_inner"],
        max_position_embeddings=cfg["n_positions"],
        layer_norm_eps=cfg["layer_norm_epsilon"]))
    if role == "serve":
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16",
                                    master_weight=False)
        model.eval()
    return model


# ----------------------------------------------------------- operations

def matmul_params(cfg: dict, layers: int) -> int:
    h, f = cfg["n_embd"], cfg["n_inner"]
    return layers * (3 * h * h + h * h + 2 * h * f)


def head_params(cfg: dict) -> int:
    return cfg["n_embd"] * cfg["vocab_size"]


def attn_flops_per_layer(cfg: dict, q_tokens: float, ctx_sum: float) -> float:
    del q_tokens
    return 4.0 * cfg["n_embd"] * ctx_sum


def train_flops_per_step(cfg: dict, layers: int, batch: int, seq: int) -> float:
    tokens = batch * seq
    dense = 6.0 * (matmul_params(cfg, layers) + head_params(cfg)) * tokens
    ctx = batch * seq * (seq + 1) / 2.0
    return dense + 3.0 * layers * attn_flops_per_layer(cfg, tokens, ctx)


def attention_shape(cfg: dict) -> dict:
    nh = cfg["n_head"]
    return {"heads": nh, "kv_heads": nh, "head_dim": cfg["n_embd"] // nh}


# ------------------------------------------------------ reference trainer

_LAYER_KEYS = {"ln1_w": "ln_1.weight", "ln1_b": "ln_1.bias",
               "qkv": "attn.qkv_proj.weight", "o": "attn.out_proj.weight",
               "ln2_w": "ln_2.weight", "ln2_b": "ln_2.bias",
               "fc_in": "fc_in.weight", "fc_out": "fc_out.weight"}


def reference_grads(cfg: dict, layers: int, params: dict, ids, on_grad,
                    precision: str = "f32") -> float:
    """As families/llama.py's: one float32 forward and backward over
    `ids` [B, S], labels = ids, gradients handed out a leaf at a time."""
    import jax
    import jax.numpy as jnp

    from ..reference_train import blocked_head, token_losses

    f32 = jnp.float32
    mm = _mm(precision)
    nh = cfg["n_head"]
    hd = cfg["n_embd"] // nh
    eps = cfg["layer_norm_epsilon"]
    ids = jnp.asarray(ids, jnp.int32)
    s = ids.shape[1]

    def ln(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * g + b

    @jax.checkpoint
    def attend(qkv):
        q, k, v = qkv                             # [S, hd] each
        sc = (q @ k.T) * hd ** -0.5
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        return jax.nn.softmax(sc, axis=-1) @ v

    def block(x, lw):
        b = x.shape[0]
        qkv = mm(ln(x, lw["ln1_w"], lw["ln1_b"]), lw["qkv"])
        qkv = qkv.reshape(b, s, 3, nh, hd).transpose(2, 0, 3, 1, 4)
        a = jax.lax.map(attend, tuple(
            t.reshape(b * nh, s, hd) for t in (qkv[0], qkv[1], qkv[2])))
        a = a.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)
        x = x + mm(a.reshape(b, s, nh * hd), lw["o"])
        y = ln(x, lw["ln2_w"], lw["ln2_b"])
        return x + mm(jax.nn.gelu(mm(y, lw["fc_in"]), approximate=False),
                      lw["fc_out"])

    def layer_w(i):
        return {k: params[f"blocks.{i}.{n}"] for k, n in _LAYER_KEYS.items()}

    def head_rows(xr, idr, gbw):
        return token_losses(mm(ln(xr, gbw[0], gbw[1]), gbw[2]), idr)

    fwd = jax.jit(block)
    bwd = jax.jit(lambda x, lw, dx: jax.vjp(block, x, lw)[1](dx))
    with jax.default_matmul_precision("highest"):
        wte, wpe = params["wte.weight"], params["wpe.weight"]
        xs = [jax.jit(lambda e, p: e[ids] + p[:s][None])(wte, wpe)]
        for i in range(layers):
            xs.append(fwd(xs[-1], layer_w(i)))
        loss, dx, (g_w, g_b, g_head) = blocked_head(
            head_rows, xs.pop(), ids,
            (params["ln_f.weight"], params["ln_f.bias"],
             params["lm_head.weight"]))
        on_grad("lm_head.weight", g_head)
        on_grad("ln_f.weight", g_w)
        on_grad("ln_f.bias", g_b)
        del g_head, g_w, g_b
        for i in reversed(range(layers)):
            dx, g = bwd(xs.pop(), layer_w(i), dx)
            for k, n in _LAYER_KEYS.items():
                on_grad(f"blocks.{i}.{n}", g[k])
            del g
        on_grad("wte.weight", jax.jit(
            lambda d: jnp.zeros(wte.shape, f32).at[ids].add(d))(dx))
        on_grad("wpe.weight", jax.jit(
            lambda d: jnp.zeros(wpe.shape, f32).at[:s].add(d.sum(0)))(dx))
    return loss

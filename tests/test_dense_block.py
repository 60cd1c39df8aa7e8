"""`text/models/dense_block.py`: the ONE dense decoder block.

(a) every dense program of the three modules reaches `dense_block.block`
    from its layer body — seven sites, two architectures. A block written
    out an eighth time somewhere fails its case here.
(b) the block with the sequence `attend` gives the Layers' logits
    (`LlamaForCausalLM` / `GPTForCausalLM` are the independent reference).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.text.models import dense_block as db
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.text.models.llama import LlamaConfig, LlamaForCausalLM

N_L, H, F, NH, HD, V, MAX_POS = 2, 32, 64, 4, 8, 96, 64
BS, PAGES, BLOCKS = 8, 4, 9          # the paged pool: 4 pages a slot
B, S, C, T = 2, 8, 3, 17             # rows, prompt, candidates, dense cache


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _spec(arch, nkv, **kw):
    base = dict(num_layers=N_L, num_heads=NH, num_kv_heads=nkv, head_dim=HD,
                rope_theta=1e4, rms_eps=1e-5, max_new_tokens=0,
                do_sample=False, top_k=0, top_p=1.0, temperature=1.0,
                eos_token_id=-1, tie_embeddings=False, arch=arch)
    base.update(kw)
    return db._GenSpec(**base)


def _params(arch, nkv):
    if arch == "gpt":
        layers = {"ln1_w": (H,), "ln1_b": (H,), "qkv": (H, 3 * H),
                  "o": (H, H), "ln2_w": (H,), "ln2_b": (H,),
                  "fc_in": (H, F), "fc_out": (F, H)}
        top = {"wpe": (MAX_POS, H), "final_ln_b": (H,)}
    else:
        layers = {"q": (H, NH * HD), "k": (H, nkv * HD), "v": (H, nkv * HD),
                  "o": (NH * HD, H), "gate": (H, F), "up": (H, F),
                  "down": (F, H), "input_ln": (H,), "post_ln": (H,)}
        top = {"rope_cos": (MAX_POS, HD), "rope_sin": (MAX_POS, HD)}
    top.update(embed=(V, H), final_ln=(H,), lm_head=(H, V))
    params = {k: _sds(s) for k, s in top.items()}
    params["layers"] = {k: _sds((N_L,) + s) for k, s in layers.items()}
    return params


def _site_programs(arch):
    """site -> (the program's function, static arguments bound; abstract
    operands; the shape of x its layer body hands the block)."""
    from paddle_tpu.inference import engine, speculative
    from paddle_tpu.text import generation

    nkv = NH if arch == "gpt" else 2
    spec, params = _spec(arch, nkv), _params(arch, nkv)
    i32 = jnp.int32
    pool = _sds((N_L, BLOCKS, nkv, BS, HD))
    dense = _sds((N_L, B, T, nkv, HD))
    key = _sds((2,), jnp.uint32)
    last = _sds((B + 1,), i32)             # each slot's last token

    def samp(b):
        return {"do_sample": _sds((b,), jnp.bool_),
                "temperature": _sds((b,)), "top_k": _sds((b,), i32),
                "top_p": _sds((b,))}

    paged = (spec, BS, "model", False)
    # the paged programs take what the host decides a call as ONE int32
    # operand: scalars, the block table row(s), the ids
    return {
        "whole_prompt_prefill": (
            functools.partial(engine._prefill_impl, *paged, PAGES),
            (params, _sds((1 + PAGES + S,), i32), pool, pool, None, None,
             samp(1), key), (1, S, H)),
        "static_decode": (
            lambda p, ids, k, n: generation._generate_program.__wrapped__(
                p, ids, _spec(arch, nkv, max_new_tokens=4), k, n),
            (params, _sds((B, S), i32), key, _sds((), i32)), (B, H)),
        "paged_decode": (
            functools.partial(engine._decode_step_impl, *paged),
            (params, _sds((B, 3 + PAGES), i32), pool, pool, None, None,
             last, samp(B), key), (B, H)),
        "paged_chunk": (
            functools.partial(engine._chunk_prefill_impl, *paged, True, 2,
                              PAGES),
            (params, _sds((6 + PAGES + S,), i32), pool, pool, None, None,
             last, samp(1), key), (S, H)),
        "paged_verify": (
            functools.partial(engine._spec_verify_impl, *paged, PAGES),
            (params, _sds((B, 2 + PAGES + C), i32), pool, pool, None, None,
             samp(B), key), (B, C, H)),
        "draft_decode": (
            functools.partial(speculative._draft_propose_impl, spec, 4),
            (params, _sds((B, C), i32), _sds((B,), i32), _sds((B,), i32),
             dense, dense), (B, H)),
        "static_verify": (
            functools.partial(speculative._dense_verify_impl, spec),
            (params, _sds((B, C), i32), _sds((B,), i32), dense, dense),
            (B, C, H)),
    }


SITES = ("whole_prompt_prefill", "static_decode", "paged_decode",
         "paged_chunk", "paged_verify", "draft_decode", "static_verify")


@pytest.mark.parametrize("arch", ["llama", "gpt"])
@pytest.mark.parametrize("site", SITES)
def test_every_dense_program_reaches_the_one_block(monkeypatch, site, arch):
    seen = []
    block = db.block

    def counted(x, lw, spec, attend, rope=None):
        seen.append(x.shape)
        return block(x, lw, spec, attend, rope)

    monkeypatch.setattr(db, "block", counted)
    fn, operands, x_shape = _site_programs(arch)[site]
    jax.eval_shape(fn, *operands)
    assert x_shape in seen, (site, arch, seen)


def _tiny(arch):
    paddle.seed(0)
    if arch == "gpt":
        m = GPTForCausalLM(GPTConfig(
            vocab_size=V, hidden_size=H, num_hidden_layers=N_L,
            num_attention_heads=NH, max_position_embeddings=MAX_POS))
    else:
        m = LlamaForCausalLM(LlamaConfig(
            vocab_size=V, hidden_size=H, intermediate_size=F,
            num_hidden_layers=N_L, num_attention_heads=NH,
            num_key_value_heads=2, max_position_embeddings=MAX_POS))
    m.eval()
    return m


@pytest.mark.parametrize("arch", ["llama", "gpt"])
def test_block_over_a_sequence_gives_the_layers_logits(arch):
    from paddle_tpu.inference.speculative import _spec_and_params

    model = _tiny(arch)
    spec, params = _spec_and_params(model)
    ids = np.random.RandomState(3).randint(0, V, (2, 11)).astype(np.int64)
    want = np.asarray(model(paddle.to_tensor(ids))._data, np.float32)
    x, ks, vs = db.forward_sequence(params, jnp.asarray(ids, jnp.int32),
                                    spec)
    got = db._logits(x.reshape(-1, H), params, spec).reshape(want.shape)
    assert ks.shape == vs.shape == (N_L, 2, 11, spec.num_kv_heads, HD)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)

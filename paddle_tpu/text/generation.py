"""Autoregressive decoding engine — KV cache + single-program generation.

Reference parity: the decode-attention family the reference ships as fused
CUDA kernels — masked_multihead_attention
(/root/reference/paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu),
block_multihead_attention (fusion/gpu/block_multi_head_attention_kernel.cu) —
plus the PaddleNLP `generate()` loop those kernels serve.

TPU-native design (NOT a kernel translation):
  - The ENTIRE generation — prefill + every decode step — is ONE compiled
    XLA program: `lax.scan` over decode steps, `lax.scan` over the stacked
    layer weights inside each step. A per-token Python loop pays the
    host's dispatch cost per token; the fused program pays it once per
    SEQUENCE.
  - KV cache is a static-shaped buffer [L, B, T, H_kv, D] updated with
    `lax.dynamic_update_slice` — static shapes keep XLA happy; the valid
    region is tracked by a scalar position (the masked_multihead_attention
    role: seq-1 query attending to the cache under a length mask).
  - Prefill rides the Pallas flash kernel (ops/pallas_attention.py) on TPU.
  - The layer's mathematics is `models/dense_block.block`, which the paged
    engine's and the speculative programs call too; this module adds the
    two `attend`s it knows: the sequence in hand
    (`dense_block.forward_sequence`, no cache) and the dense cache at one
    scalar position (the decode scan's). Weight extraction and stacking
    (`_stacked_params*`, `_STACK_CACHE`) live here for both engines.
  - Prompt lengths bucket via jit.default_buckets so a serving stream
    compiles O(log S) programs, keyed by (bucket, B, sampling config).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .models import dense_block as db
from .models.dense_block import _GenSpec, _logits


def _rope_tables_np(max_len, head_dim, theta, dtype):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim))
    t = np.arange(max_len, dtype=np.float64)
    freqs = np.outer(t, inv)
    emb = np.concatenate([freqs, freqs], axis=-1)  # [T, D]
    return (np.cos(emb).astype(dtype), np.sin(emb).astype(dtype))


def _quantize_w(w):
    """Per-output-channel symmetric int8 for a [K, N] weight — delegates to
    the public weight_quantize rule so serving and the quant API can never
    drift numerically."""
    from ..incubate.nn.functional import weight_quantize_raw

    return weight_quantize_raw(w)


def _quantize_w4(w):
    """TRUE packed int4 (two nibbles per byte) with per-output-channel
    scales — the same rule weight_quantize(algo="weight_only_int4") applies
    (ops/quantized.quantize_int4 handles stacked [L, K, N] weights
    directly: every axis rule is relative to the trailing two dims)."""
    from ..ops.quantized import quantize_int4

    return quantize_int4(w)


def _sample_token(logits, key, spec: _GenSpec):
    """Greedy or (temperature, top-k, top-p) sampling. logits [B, V]."""
    if not spec.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / max(spec.temperature, 1e-6)
    if spec.top_k > 0:
        kth = jax.lax.top_k(lg, spec.top_k)[0][..., -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    if spec.top_p < 1.0:
        srt = jnp.sort(lg, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumsum(prev) < p (nucleus incl.
        # the boundary token, matching ops/extras.top_p_sampling)
        keep = cum - probs < spec.top_p
        cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                         keepdims=True)
        lg = jnp.where(lg < cutoff, -jnp.inf, lg)
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)


#: host-side mirror of the generation program keys — a NEW key here
#: records a compile event for the obs watchdog. Kept separate from the
#: executable cache below so tests can clear the event mirror without
#: forcing a real recompile (the obs watchdog fire/no-fire pairs do).
_seen_gen_programs: set = set()

#: round 14: the generation engine owns its executables via the AOT path
#: (_generate_program.lower().compile()) — the compiled object carries
#: XLA cost_analysis()/memory_analysis() into the obs cost ledger for
#: free, and the compile wall is measured exactly instead of smeared
#: into the first generate() call. prog_key -> (compiled, ProgramCost)
_gen_executables: dict = {}


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=())
def _generate_program(params, ids, spec: _GenSpec, rng_key, true_len):
    """The fused prefill+decode program. ids [B, S_bucket] int32, right-
    padded to the prompt bucket; `true_len` (traced scalar) is the real
    prompt length, so the program is keyed by (bucket, B, spec) — a serving
    stream compiles O(log S) programs, not one per distinct prompt length.
    Padded prefill positions produce garbage K/V at cache slots
    [true_len, S_bucket); decode writes start at true_len and the
    `arange <= pos` mask never reaches an unwritten slot, so the garbage is
    progressively overwritten and never attended to.
    Returns tokens [B, max_new_tokens] int32."""
    s = ids.shape[1]
    total = s + spec.max_new_tokens
    x, ks, vs = db.forward_sequence(params, ids, spec)
    # static-shaped cache for the whole generation
    pad = total - s
    kcache = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    vcache = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))

    # the last REAL prompt position, not the last padded one
    x_last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)[:, 0]
    logits0 = _logits(x_last, params, spec)
    key0, sub = jax.random.split(rng_key)
    tok0 = _sample_token(logits0, sub, spec)
    finished0 = tok0 == spec.eos_token_id

    def step(carry, _):
        tok, kc, vc, pos, key, finished = carry
        xt, rope = db.embed(params, tok, pos, spec)       # [B, H]

        def layer(xc, per_layer):
            lw, *kv = per_layer

            def attend(q, k, v):
                # the dense cache [B, T, H_kv, D] at ONE scalar position:
                # write there, attend under the length mask
                z = jnp.int32(0)
                kv[0] = jax.lax.dynamic_update_slice(kv[0], k[:, None],
                                                     (z, pos, z, z))
                kv[1] = jax.lax.dynamic_update_slice(kv[1], v[:, None],
                                                     (z, pos, z, z))
                return db.attend_one(q, *kv, jnp.arange(total) <= pos)

            return db.block(xc, lw, spec, attend, rope), tuple(kv)

        xt, (kc, vc) = jax.lax.scan(layer, xt, (params["layers"], kc, vc))
        lg = _logits(xt, params, spec)
        key, sub2 = jax.random.split(key)
        nxt = _sample_token(lg, sub2, spec)
        nxt = jnp.where(finished, spec.eos_token_id, nxt)
        finished = finished | (nxt == spec.eos_token_id)
        return (nxt, kc, vc, pos + 1, key, finished), tok

    # scan max_new_tokens-1 steps and append the final carried token: the
    # last sampled token needs no forward pass of its own (a full-length
    # scan would run one dead per-layer forward whose sample is discarded)
    (last_tok, _, _, _, _, _), toks = jax.lax.scan(
        step, (tok0, kcache, vcache, true_len.astype(jnp.int32), key0,
               finished0),
        None, length=spec.max_new_tokens - 1)
    toks = jnp.swapaxes(toks, 0, 1)                   # [B, new-1]
    return jnp.concatenate([toks, last_tok[:, None]], axis=1)


_STACK_CACHE: dict = {}
_STACK_CACHE_MAX = 2  # stacked weights are a full model-size copy; bound it


def _cached_extract(model, extract_fn, tag=""):
    """Stack-cache wrapper: key = per-buffer monotonic version
    (Tensor._buf_version — bumped by every construction and every
    buffer-swap mutation, never reused). id() is deliberately NOT part of
    the key: CPython reuses freed addresses, so a training step followed by
    allocation could produce the same id set and silently serve stale
    stacked weights."""
    sd = {k: v for k, v in model.state_dict().items()}
    key = (tag,) + tuple((k, sd[k]._buf_version) for k in sorted(sd))
    hit = _STACK_CACHE.get((id(model), tag))
    if hit is not None and hit[0] == key:
        return hit[1]
    params = extract_fn(sd)
    _STACK_CACHE[(id(model), tag)] = (key, params)
    while len(_STACK_CACHE) > _STACK_CACHE_MAX:
        _STACK_CACHE.pop(next(iter(_STACK_CACHE)))
    return params


def _stacked_params(model, weight_quant="none"):
    """Extract + stack per-layer weights [L, ...] for lax.scan (cached,
    see _cached_extract). weight_quant="int8"/"int4" stores the seven
    layer matmul weights and lm_head as weight-only pairs (see _mm; int4
    is true packed-nibble storage)."""
    cfg = model.config
    return _cached_extract(
        model, lambda sd: _extract_llama(cfg, sd, weight_quant),
        tag=weight_quant)


def _extract_llama(cfg, sd, weight_quant="none"):
    def w(name):
        return sd[name]._data

    prefix = "model." if any(k.startswith("model.") for k in sd) else "llama."
    layers = {"q": [], "k": [], "v": [], "o": [], "gate": [], "up": [],
              "down": [], "input_ln": [], "post_ln": []}
    for i in range(cfg.num_hidden_layers):
        base = f"{prefix}layers.{i}."
        layers["q"].append(w(base + "self_attn.q_proj.weight"))
        layers["k"].append(w(base + "self_attn.k_proj.weight"))
        layers["v"].append(w(base + "self_attn.v_proj.weight"))
        layers["o"].append(w(base + "self_attn.o_proj.weight"))
        layers["gate"].append(w(base + "mlp.gate_proj.weight"))
        layers["up"].append(w(base + "mlp.up_proj.weight"))
        layers["down"].append(w(base + "mlp.down_proj.weight"))
        layers["input_ln"].append(w(base + "input_layernorm.weight"))
        layers["post_ln"].append(w(base + "post_attention_layernorm.weight"))
    quant = weight_quant in ("int8", "int4")
    qfn = _quantize_w4 if weight_quant == "int4" else _quantize_w

    def stack(k, vals):
        stacked = jnp.stack(vals)
        if quant and k not in ("input_ln", "post_ln"):
            if weight_quant == "int4":
                # quantize_int4's axis rules are trailing-dim-relative, so
                # the stacked [L, K, N] tensor quantizes in one call
                return qfn(stacked)
            # vmap the per-channel quantizer over the layer axis
            return jax.vmap(qfn)(stacked)
        return stacked

    params = {
        "embed": w(prefix + "embed_tokens.weight"),
        "final_ln": w(prefix + "norm.weight"),
        "layers": {k: stack(k, v) for k, v in layers.items()},
    }
    if not cfg.tie_word_embeddings:
        head = w("lm_head.weight")
        params["lm_head"] = qfn(head) if quant else head
    cos, sin = _rope_tables_np(cfg.max_position_embeddings, cfg.head_dim,
                               cfg.rope_theta,
                               np.dtype(params["embed"].dtype).name
                               if params["embed"].dtype != jnp.bfloat16
                               else "float32")
    params["rope_cos"] = jnp.asarray(cos, params["embed"].dtype)
    params["rope_sin"] = jnp.asarray(sin, params["embed"].dtype)
    return params


def _stacked_params_gpt(model, weight_quant="none"):
    """GPT-family extraction: LN weights/biases, fused qkv, learned wpe.
    weight_quant="int8"/"int4" stores qkv/o/fc_in/fc_out + lm_head as
    weight-only pairs (see _mm)."""
    cfg = model.config
    return _cached_extract(
        model, lambda sd: _extract_gpt(cfg, sd, weight_quant),
        tag=weight_quant)


def _extract_gpt(cfg, sd, weight_quant="none"):
    def w(name):
        return sd[name]._data

    layers = {"ln1_w": [], "ln1_b": [], "qkv": [], "o": [], "ln2_w": [],
              "ln2_b": [], "fc_in": [], "fc_out": []}
    for i in range(cfg.num_hidden_layers):
        base = f"blocks.{i}."
        layers["ln1_w"].append(w(base + "ln_1.weight"))
        layers["ln1_b"].append(w(base + "ln_1.bias"))
        layers["qkv"].append(w(base + "attn.qkv_proj.weight"))
        layers["o"].append(w(base + "attn.out_proj.weight"))
        layers["ln2_w"].append(w(base + "ln_2.weight"))
        layers["ln2_b"].append(w(base + "ln_2.bias"))
        layers["fc_in"].append(w(base + "fc_in.weight"))
        layers["fc_out"].append(w(base + "fc_out.weight"))
    quant = weight_quant in ("int8", "int4")
    qfn = _quantize_w4 if weight_quant == "int4" else _quantize_w
    qkeys = ("qkv", "o", "fc_in", "fc_out")

    def stack(k, vals):
        stacked = jnp.stack(vals)
        if quant and k in qkeys:
            return qfn(stacked) if weight_quant == "int4" \
                else jax.vmap(qfn)(stacked)
        return stacked

    head = w("lm_head.weight")
    params = {
        "embed": w("wte.weight"),
        "wpe": w("wpe.weight"),
        "final_ln": w("ln_f.weight"),
        "final_ln_b": w("ln_f.bias"),
        "lm_head": qfn(head) if quant else head,
        "layers": {k: stack(k, v) for k, v in layers.items()},
    }
    return params


def generate(model, input_ids, max_new_tokens=32, max_length=None,
             do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
             eos_token_id=None, seed=None, weight_quant="none",
             engine="static", prefix_cache=None, spec_decode=None):
    """Autoregressive generation with a static KV cache, greedy or sampled.

    Returns a Tensor [B, prompt_len + n_generated] (prompt included, like
    the reference ecosystem's generate with full-sequence output).

    engine="static" (default): the whole loop is one compiled XLA program
    keyed by (batch, prompt bucket, generation-length bucket, sampling
    config). engine="paged": the continuous-batching serving engine
    (inference/engine.py) over the block-paged KV cache — same greedy
    tokens, the serving route for streams of requests. `prefix_cache`
    overrides FLAGS_prefix_cache for the paged engine (shared prompt
    prefixes across the batch/stream reuse KV blocks; greedy tokens are
    identical either way). `spec_decode` turns on speculative decoding
    (inference/speculative.py): for engine="paged" it is forwarded to
    the ServingEngine (string or SpecConfig); for engine="static" only
    the greedy n-gram proposer is wired ("ngram" | SpecConfig) — tokens
    stay identical to the non-speculative run either way.
    """
    from ..core.tensor import Tensor

    cfg = model.config
    ids = np.asarray(input_ids._data if hasattr(input_ids, "_data")
                     else input_ids).astype(np.int32)
    if ids.ndim == 1:
        ids = ids[None]
    if max_length is not None:
        max_new_tokens = int(max_length) - ids.shape[1]
    if max_new_tokens <= 0:
        raise ValueError("max_new_tokens must be positive")
    total = ids.shape[1] + int(max_new_tokens)
    if total > int(cfg.max_position_embeddings):
        # positional tables (wpe / rope) end here; indexing past them would
        # silently clamp to the last row under jit
        raise ValueError(
            f"prompt ({ids.shape[1]}) + max_new_tokens ({max_new_tokens}) "
            f"= {total} exceeds max_position_embeddings "
            f"({cfg.max_position_embeddings})")
    if engine not in ("static", "paged"):
        raise ValueError(f"engine must be 'static' or 'paged', got "
                         f"{engine!r}")
    # models declare their engine arch; default is the llama layout
    arch = getattr(model, "_gen_arch", "llama")
    if hasattr(model, "serving_arrays") and engine == "static":
        raise ValueError(
            f"the static single-program engine does not know {arch} (a "
            "model that declares its layers one by one, each with its "
            "own kind of cache): pass engine=\"paged\" or put the model "
            "behind ServingEngine")
    from ..core.flags import flag

    if weight_quant in (None, "none"):
        # the serving-wide default: per-call weight_quant= overrides
        weight_quant = str(flag("FLAGS_weight_only_dtype"))
    if weight_quant not in ("none", "int8", "int4"):
        raise ValueError(f"weight_quant must be 'none', 'int8' or 'int4', "
                         f"got {weight_quant!r}")
    mnt = int(max_new_tokens)
    if engine == "paged":
        # the paged engine addresses context through whole KV blocks, so
        # its usable length is max_position_embeddings rounded DOWN to the
        # block size — surface the gap here, at the API boundary, instead
        # of deep inside the engine's admission check

        kv_bs = int(flag("FLAGS_kv_block_size"))
        usable = (int(cfg.max_position_embeddings) // kv_bs) * kv_bs
        if total > usable:
            raise ValueError(
                f"prompt ({ids.shape[1]}) + max_new_tokens "
                f"({max_new_tokens}) = {total} exceeds the paged engine's "
                f"usable context ({usable} = max_position_embeddings "
                f"rounded down to whole {kv_bs}-token kv blocks); use "
                "engine='static' or a smaller generation budget")
        from ..inference.engine import generate_paged

        toks = generate_paged(model, ids.astype(np.int64), mnt,
                              do_sample=bool(do_sample),
                              temperature=float(temperature),
                              top_k=int(top_k), top_p=float(top_p),
                              eos_token_id=eos_token_id,
                              seed=None if seed is None else int(seed),
                              prefix_cache=prefix_cache,
                              spec_decode=spec_decode,
                              weight_quant=str(weight_quant))
        return _assemble_output(ids, toks, eos_token_id, Tensor)
    if prefix_cache is not None:
        raise ValueError("prefix_cache applies to engine='paged' only "
                         "(the static engine holds no block pool)")
    if spec_decode not in (None, "off"):
        if do_sample:
            raise NotImplementedError(
                "static-engine speculative decoding is greedy-only; "
                "rejection sampling rides engine='paged'")
        if weight_quant != "none":
            raise NotImplementedError(
                "static-engine speculative decoding runs unquantized "
                "weights")
        # deferred import: inference.speculative imports from this module
        from ..inference.speculative import (SpecConfig,
                                             generate_static_spec)

        sc = spec_decode if isinstance(spec_decode, SpecConfig) \
            else SpecConfig(method=str(spec_decode))
        if sc.method != "ngram" or sc.proposer is not None:
            raise NotImplementedError(
                "the static engine wires the n-gram proposer only; "
                "draft-model speculation rides engine='paged'")
        toks = generate_static_spec(model, ids, mnt,
                                    eos_token_id=eos_token_id, k=sc.k,
                                    max_ngram=sc.max_ngram)
        return _assemble_output(ids, toks, eos_token_id, Tensor)
    from ..jit.api import default_buckets

    s_true = ids.shape[1]
    # bucket the GENERATION length too: _GenSpec used to key a fresh
    # program per exact max_new_tokens — a serving stream of varied
    # lengths now compiles O(log L) programs, trading ≤2x dead decode
    # steps (the tail is trimmed below; eos masking is unchanged)
    mnt_bucket = min(default_buckets(mnt),
                     int(cfg.max_position_embeddings) - s_true)
    mnt_bucket = max(mnt_bucket, mnt)
    if arch == "gpt":
        nh = cfg.num_attention_heads
        spec = _GenSpec(
            num_layers=cfg.num_hidden_layers, num_heads=nh, num_kv_heads=nh,
            head_dim=cfg.hidden_size // nh, rope_theta=0.0,
            rms_eps=cfg.layer_norm_eps,
            max_new_tokens=mnt_bucket, do_sample=bool(do_sample),
            top_k=int(top_k), top_p=float(top_p),
            temperature=float(temperature),
            eos_token_id=int(eos_token_id if eos_token_id is not None
                             else -1),
            tie_embeddings=False, arch="gpt",
            weight_quant=str(weight_quant))
        params = _stacked_params_gpt(model, weight_quant=str(weight_quant))
    else:
        spec = _GenSpec(
            num_layers=cfg.num_hidden_layers,
            num_heads=cfg.num_attention_heads,
            num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, rms_eps=cfg.rms_norm_eps,
            max_new_tokens=mnt_bucket, do_sample=bool(do_sample),
            top_k=int(top_k), top_p=float(top_p),
            temperature=float(temperature),
            eos_token_id=int(eos_token_id if eos_token_id is not None
                             else -1),
            tie_embeddings=bool(cfg.tie_word_embeddings),
            weight_quant=str(weight_quant))
        params = _stacked_params(model, weight_quant=str(weight_quant))
    if seed is not None:
        key = jax.random.PRNGKey(int(seed))
    else:
        from ..core.rng import next_key

        key = next_key()
    # pad the prompt up to its bucket so the compiled program is keyed by
    # (bucket, B, spec): O(log S) compilations per serving stream. The
    # bucket is clamped so the padded total still fits the position tables.
    bucket = min(default_buckets(s_true),
                 int(cfg.max_position_embeddings) - mnt_bucket)
    bucket = max(bucket, s_true)
    ids_padded = np.pad(ids, ((0, 0), (0, bucket - s_true))) \
        if bucket > s_true else ids
    # compile watchdog + AOT executable cache: the generation program is
    # keyed by (spec, shapes, param avals) — the host key now addresses
    # the REAL compiled executable, not a mirror of jax.jit's cache.
    # This is the site whose round-10 failure (a program per exact
    # max_new_tokens) motivated the watchdog: exact-length keying shows
    # up as a recompile-storm finding instead of an accidental
    # discovery, and since round 14 every program also lands in the obs
    # cost ledger (flops / bytes accessed from the compiled object).
    import time as _time

    params_fp = tuple((tuple(p.shape), str(p.dtype))
                      for p in jax.tree_util.tree_leaves(params))
    prog_key = (spec, ids_padded.shape, str(params["embed"].dtype),
                params_fp)
    import hashlib

    key_str = (f"b{ids_padded.shape[0]}/s{bucket}/g{spec.max_new_tokens}/"
               f"sample{int(spec.do_sample)}/p"
               + hashlib.sha1(repr(params_fp).encode()).hexdigest()[:8])
    exe_cost = _gen_executables.get(prog_key)
    compile_wall = 0.0
    if exe_cost is None:
        from ..obs import costs as _costs

        _t0 = _time.perf_counter()
        exe = _generate_program.lower(
            params, jnp.asarray(ids_padded), spec, key,
            jnp.int32(s_true)).compile()
        compile_wall = _time.perf_counter() - _t0
        entry = _costs.record_program(
            "generate", f"generate/{arch}", key_str, compiled=exe,
            wall_s=compile_wall, bucket=bucket)
        exe_cost = (exe, entry)
        _gen_executables[prog_key] = exe_cost
    exe, entry = exe_cost
    if prog_key not in _seen_gen_programs:
        _seen_gen_programs.add(prog_key)
        from ..obs.watchdog import record_compile

        record_compile(
            "generate", f"generate/{arch}", key_str,
            bucket=(bucket, spec.max_new_tokens), wall_s=compile_wall,
            cost=({"flops": entry.flops,
                   "bytes_accessed": entry.bytes_accessed,
                   "peak_hbm_bytes": entry.peak_hbm_bytes}
                  if entry.analyzed else None))
    _t_run = _time.perf_counter()
    toks = exe(params, jnp.asarray(ids_padded), key, jnp.int32(s_true))
    # drop the bucketed tail: tokens [mnt, mnt_bucket) are dead steps the
    # length bucketing trades for program reuse
    toks = np.asarray(jax.device_get(toks))[:, :mnt]
    entry.observe(_time.perf_counter() - _t_run)
    return _assemble_output(ids, toks, eos_token_id, Tensor)


def _assemble_output(ids, toks, eos_token_id, Tensor):
    """Shared static/paged postprocessing: trim columns past the point
    where every row finished, prepend the prompt."""
    if eos_token_id is not None:
        # trim columns past the point where every row finished
        done = (toks == int(eos_token_id))
        all_done = done.all(axis=0)
        keep = len(all_done)
        first = np.argmax(all_done) if all_done.any() else None
        if first is not None and all_done[first]:
            keep = first + 1
        toks = toks[:, :keep]
    full = np.concatenate([ids, toks], axis=1)
    return Tensor(jnp.asarray(full.astype(np.int64)), _internal=True,
                  stop_gradient=True)

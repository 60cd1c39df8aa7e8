"""Speculative decoding — draft proposers + the greedy token-parity oracle.

Decode on this stack is pure HBM bandwidth (the D8 cost ledger gates the
~103 GB/s roofline measurement), so per-tick throughput is capped at one
weight+KV sweep per generated token. Speculative decoding breaks that cap:
a cheap DRAFT proposes K candidate tokens, the target model scores all
K+1 candidate positions in ONE batched paged-attention pass (the verify
program in inference/engine.py — same weight sweep as a single decode
tick), and the Leviathan-et-al. accept/reject rule emits between 1 and
K+1 tokens per sweep with the output distribution provably unchanged:

  * greedy rows accept the longest prefix of proposals matching the
    verifier's own argmax, then emit the verifier's correction (or, when
    everything matched, its bonus token) — the emitted stream is
    TOKEN-IDENTICAL to the non-speculative engine by construction, which
    is the in-repo correctness oracle;
  * sampling rows accept proposal x with probability p(x) under the
    row's filtered (temperature/top-k/top-p) distribution and resample
    rejections from the residual — exactly p at every position because
    the draft proposes deterministically (a point-mass q).

This module owns everything above the verify program (every program
here runs `text/models/dense_block.block` with an `attend` over a DENSE
cache: per-row write and mask positions for the draft, `[B, C]`
candidate positions for the static verify): the SpecConfig
selection surface, the two proposers behind one interface (the
model-free n-gram/prompt-lookup proposer and the small-draft-model
proposer with its own slot-free cached state), and the static
single-program engine's speculative loop (`generate_static_spec`) so
`Model.generate(engine="static", spec_decode="ngram")` gets the same
win without a serving engine.

Cache rollback is the paged cache's stale-data contract doing the work:
rejected candidates' K/V stays in the pages, but the engine simply does
not advance `kv_len` past the accepted prefix — reads are bounded by
length masks, and the next verify window REWRITES the same positions
(idempotent re-derivation) before any mask exposes them. Nothing is
erased, nothing rejected is ever attended, and prefix-cache
registration (full blocks of `prompt + tokens[:-1]`) only ever covers
emitted tokens.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..text.generation import _stacked_params, _stacked_params_gpt
from ..text.models import dense_block as db
from ..text.models.dense_block import _GenSpec, _logits


# ------------------------------------------------------------ config

@dataclasses.dataclass(eq=False)
class SpecConfig:
    """Speculative-decoding selection surface (FLAGS_spec_decode is the
    string shorthand: engine(spec_decode="ngram") == SpecConfig("ngram")).

    method       "ngram" (model-free prompt lookup) | "draft" (a small
                 registered text model proposes; pass it as draft_model)
    k            speculation depth — tokens proposed per verify window
                 (None reads FLAGS_spec_k)
    draft_model  the proposer model for method="draft"
    max_ngram    longest suffix n-gram the lookup proposer matches
    proposer     explicit Proposer instance override (tests/fixtures:
                 e.g. the always-reject D16 fire fixture) — when set,
                 `method` is ignored
    """
    method: str = "ngram"
    k: int | None = None
    draft_model: object = None
    max_ngram: int = 3
    proposer: object = None

    def __post_init__(self):
        from ..core.flags import flag

        if self.k is None:
            self.k = int(flag("FLAGS_spec_k"))
        self.k = int(self.k)
        if self.k < 1:
            raise ValueError(f"SpecConfig.k must be >= 1, got {self.k}")
        if self.proposer is not None:
            return
        if self.method not in ("ngram", "draft"):
            raise ValueError(
                f"unknown speculative method {self.method!r} "
                "(expected 'ngram' or 'draft')")
        if self.method == "draft" and self.draft_model is None:
            raise ValueError(
                "SpecConfig(method='draft') needs draft_model=<model>")


def make_proposer(cfg: SpecConfig):
    """Resolve a SpecConfig into its Proposer instance."""
    if cfg.proposer is not None:
        return cfg.proposer
    if cfg.method == "ngram":
        return NgramProposer(cfg.k, max_ngram=cfg.max_ngram)
    return DraftModelProposer(cfg.draft_model, cfg.k)


# ------------------------------------------------- n-gram prompt lookup

def propose_ngram(context, k, max_ngram=3, min_ngram=1):
    """Model-free prompt-lookup proposal: match the LONGEST suffix
    n-gram of `context` (prompt + generated history) against an earlier
    occurrence and propose the up-to-k tokens that followed it. Among
    the matches, the most recent one with a FULL k-token continuation
    wins (the latest match overall usually sits near the end of a
    repetitive stream, where the continuation is truncated — proposing
    short windows there wastes most of the verify pass); if none has k
    tokens left, the earliest match maximizes the continuation. Returns
    an int64 array of 0..k tokens — empty means "no match, decode this
    one normally"."""
    ctx = np.asarray(context, np.int64).reshape(-1)
    n = int(ctx.size)
    k = int(k)
    if k < 1 or n < min_ngram + 1:
        return np.zeros(0, np.int64)
    for g in range(min(int(max_ngram), n - 1), min_ngram - 1, -1):
        pat = ctx[n - g:]
        # windows over ctx[:n-1]: every start strictly earlier than the
        # suffix's own position n-g, so the tail never matches itself
        wins = np.lib.stride_tricks.sliding_window_view(ctx[:n - 1], g)
        hits = np.nonzero((wins == pat).all(axis=1))[0]
        if hits.size:
            full = hits[hits + g + k <= n]
            i = int(full[-1]) if full.size else int(hits[0])
            return ctx[i + g: i + g + k].copy()
    return np.zeros(0, np.int64)


# ---------------------------------------------------- proposer interface

class Proposer:
    """One draft proposer driving the verify windows of a ServingEngine.

    The engine calls, per scheduler tick:
      proposals(engine, slots, reqs) -> one int64 array (possibly empty)
        per slot: the candidate continuations of `req.prompt+req.tokens`.
        An EMPTY proposal opts the slot out of speculation for this tick
        (it decodes normally).
    and per lifecycle event:
      finish(slot)  — the slot's request finished; drop any cached state.

    Proposers see only emitted (accepted/corrected) tokens via
    `req.tokens` — rejected drafts never reach them, so draft-side state
    can never diverge from the verified stream.
    """

    k = 0

    def proposals(self, engine, slots, reqs):
        raise NotImplementedError

    def finish(self, slot):
        pass


class NgramProposer(Proposer):
    """Prompt-lookup proposer: zero accelerator work, wins on repetitive
    streams (code, extraction, multi-turn chat re-quoting context)."""

    def __init__(self, k, max_ngram=3):
        self.k = int(k)
        self.max_ngram = int(max_ngram)

    def proposals(self, engine, slots, reqs):
        return [propose_ngram(
            np.concatenate([r.prompt.astype(np.int64),
                            np.asarray(r.tokens, np.int64)]),
            self.k, self.max_ngram) for r in reqs]


class AlwaysRejectProposer(Proposer):
    """D16 fire fixture: proposes `last+1+i (mod vocab)` — deliberately
    (almost) never the verifier's argmax, so acceptance collapses while
    greedy parity still holds through the correction path."""

    def __init__(self, k):
        self.k = int(k)

    def proposals(self, engine, slots, reqs):
        v = int(engine.params["embed"].shape[0])
        return [(int(r.tokens[-1]) + 1
                 + np.arange(self.k, dtype=np.int64)) % v for r in reqs]


class ReplayProposer(Proposer):
    """Test fixture: replays a known completion per request id, so every
    window accepts all K proposals deterministically (the TPOT-accounting
    pin test's accepts-all oracle)."""

    def __init__(self, k, by_rid):
        self.k = int(k)
        self.by_rid = {int(r): np.asarray(t, np.int64).reshape(-1)
                       for r, t in by_rid.items()}

    def proposals(self, engine, slots, reqs):
        out = []
        for r in reqs:
            seq = self.by_rid.get(r.rid)
            if seq is None:
                out.append(np.zeros(0, np.int64))
            else:
                done = len(r.tokens)
                out.append(seq[done: done + self.k])
        return out


# ------------------------------------------------ draft-model proposer

def _spec_and_params(model):
    """(arch _GenSpec, stacked params) for any registered text model —
    the same extraction the serving engine runs on the target model."""
    cfg = model.config
    arch = getattr(model, "_gen_arch", "llama")
    if arch == "gpt":
        nh = cfg.num_attention_heads
        spec = _GenSpec(
            num_layers=cfg.num_hidden_layers, num_heads=nh,
            num_kv_heads=nh, head_dim=cfg.hidden_size // nh,
            rope_theta=0.0, rms_eps=cfg.layer_norm_eps, max_new_tokens=0,
            do_sample=False, top_k=0, top_p=1.0, temperature=1.0,
            eos_token_id=-1, tie_embeddings=False, arch="gpt")
        return spec, _stacked_params_gpt(model)
    spec = _GenSpec(
        num_layers=cfg.num_hidden_layers, num_heads=cfg.num_attention_heads,
        num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, rms_eps=cfg.rms_norm_eps,
        max_new_tokens=0, do_sample=False, top_k=0, top_p=1.0,
        temperature=1.0, eos_token_id=-1,
        tie_embeddings=bool(cfg.tie_word_embeddings))
    return spec, _stacked_params(model)


def _draft_prefill_impl(dspec, params, ids, slot, kc, vc):
    """Prefill one request's prompt into the DRAFT cache's `slot` row.
    ids [1, S_bucket] right-padded; pad positions write garbage K/V past
    the true length that the ingest scan overwrites before any mask
    exposes them (same invariant as the target engine's prefill)."""
    _, ks, vs = db.forward_sequence(params, ids, dspec)
    ks, vs = ks[:, 0], vs[:, 0]                   # [L, S, Hkv, D]
    z = jnp.int32(0)
    kc = jax.lax.dynamic_update_slice(
        kc, ks[:, None].astype(kc.dtype), (z, slot, z, z, z))
    vc = jax.lax.dynamic_update_slice(
        vc, vs[:, None].astype(vc.dtype), (z, slot, z, z, z))
    return kc, vc


def _draft_propose_impl(dspec, steps, params, pend, plen, pos, kc, vc):
    """Ingest-then-propose for ALL draft rows in one program: scan
    `steps` seq-1 time steps; row b's step t consumes its pending
    emitted token `pend[b, t]` while `t < plen[b]` (catching the draft
    cache up to the verified stream), then free-runs on its own argmax.
    Rows with plen == 0 are inactive — their writes park on the trash
    position. ONE program per (steps, model) serves every tick
    regardless of which slots speculate, so the zero-post-warmup-compile
    audit holds. Returns (greedy [B, steps], kc, vc); the proposal for
    row b is greedy[b, plen-1 : plen-1+k]."""
    b, w = pend.shape
    t_trash = kc.shape[2] - 1
    active = plen > 0
    rows = jnp.arange(b)
    last_pos = db.num_positions(params, dspec) - 1

    def time_step(carry, t):
        last, kcc, vcc = carry
        pend_t = jax.lax.dynamic_index_in_dim(
            pend, jnp.minimum(t, w - 1), axis=1, keepdims=False)
        tok = jnp.where(t < plen, pend_t, last)
        p = pos + t
        # the per-row WRITE index: inactive rows park their writes on the
        # trash position T-1, so the batch shape never depends on which
        # slots are speculating; `mp` bounds the length mask, which for
        # live rows never reaches the trash position
        wp = jnp.where(active, jnp.minimum(p, t_trash), t_trash)
        mp = jnp.minimum(p, t_trash)
        x, rope = db.embed(params, tok, jnp.clip(p, 0, last_pos), dspec)

        def layer(xc, per_layer):
            lw, *kv = per_layer

            def attend(q, k, v):
                # the dense cache [B, T, Hkv, D] at PER-ROW positions
                kv[0] = kv[0].at[rows, wp].set(k.astype(kv[0].dtype))
                kv[1] = kv[1].at[rows, wp].set(v.astype(kv[1].dtype))
                valid = jnp.arange(t_trash + 1)[None, :] <= mp[:, None]
                return db.attend_one(q, *kv, valid)

            return db.block(xc, lw, dspec, attend, rope), tuple(kv)

        x, (kcc, vcc) = jax.lax.scan(layer, x, (params["layers"], kcc,
                                                vcc))
        g = jnp.argmax(_logits(x, params, dspec), axis=-1).astype(
            jnp.int32)
        return (g, kcc, vcc), g

    (_, kc, vc), gs = jax.lax.scan(time_step, (pend[:, 0], kc, vc),
                                   jnp.arange(steps))
    return jnp.swapaxes(gs, 0, 1), kc, vc


_draft_prefill_step = functools.partial(
    jax.jit, static_argnums=(0,), donate_argnums=(4, 5))(
        _draft_prefill_impl)
_draft_propose_step = functools.partial(
    jax.jit, static_argnums=(0, 1), donate_argnums=(6, 7))(
        _draft_propose_impl)


class DraftModelProposer(Proposer):
    """Small-draft-model proposer: runs any registered text model on its
    OWN dense cached state — one [L, max_slots, T+1, Hkv, D] K/V buffer
    (index T is the parked trash position), no paging, no slots taken
    from the target engine. Each tick it ingests the tokens the verifier
    emitted since last tick (rejected drafts never existed as far as the
    draft cache is concerned) and free-runs K greedy steps ahead.

    Programs go through engine._program, so they ride the shared AOT
    executable cache and the compile watchdog like every other serving
    program: one propose program per (k, draft fingerprint) — batch
    shape is always the full slot count — plus one prefill program per
    prompt bucket."""

    def __init__(self, draft_model, k):
        if draft_model is None:
            raise ValueError("DraftModelProposer needs a draft model")
        self.k = int(k)
        if self.k < 1:
            raise ValueError("speculation depth k must be >= 1")
        self.dspec, self.dparams = _spec_and_params(draft_model)
        self._fp = hash(
            tuple((tuple(p.shape), str(p.dtype))
                  for p in jax.tree_util.tree_leaves(self.dparams)))
        self._bound = False

    # --- lazy binding to the engine geometry (slot count, context)
    def _bind(self, engine):
        if self._bound:
            return
        tv = int(engine.params["embed"].shape[0])
        dv = int(self.dparams["embed"].shape[0])
        if tv != dv:
            raise ValueError(
                f"draft model vocab ({dv}) != target vocab ({tv}) — "
                "proposed token ids would not be target tokens")
        n = int(engine.max_slots)
        self._t = int(engine.max_model_len)
        sp = self.dspec
        shape = (sp.num_layers, n, self._t + 1, sp.num_kv_heads,
                 sp.head_dim)
        dtype = self.dparams["embed"].dtype
        self._kc = jnp.zeros(shape, dtype)
        self._vc = jnp.zeros(shape, dtype)
        self._pos = np.zeros(n, np.int64)       # next draft write index
        self._ingested = np.zeros(n, np.int64)  # emitted tokens consumed
        self._slot_rid = [None] * n
        self._dead = np.zeros(n, bool)          # out of draft context
        self._bound = True

    def _prefill(self, engine, slot, req):
        from ..jit.api import default_buckets

        s = int(req.prompt.size)
        bucket = min(max(default_buckets(s), s), self._t)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :s] = req.prompt
        args = (self.dspec, self.dparams, jnp.asarray(ids),
                jnp.int32(slot), self._kc, self._vc)
        prog, entry = engine._program(
            "serving.spec_draft_prefill", _draft_prefill_step, 1, bucket,
            False, (self._fp,), args)
        t0 = time.perf_counter()
        self._kc, self._vc = prog(*args[1:])
        entry.observe(time.perf_counter() - t0)
        self._pos[slot] = s
        self._ingested[slot] = 0
        self._slot_rid[slot] = req.rid
        self._dead[slot] = False

    def proposals(self, engine, slots, reqs):
        self._bind(engine)
        k = self.k
        w = k + 1
        steps = 2 * k  # room to ingest a full window AND free-run k ahead
        empty = np.zeros(0, np.int64)
        for slot, req in zip(slots, reqs):
            if self._slot_rid[slot] != req.rid:
                self._prefill(engine, slot, req)
        props: dict = {}
        pending: dict = {}
        for slot, req in zip(slots, reqs):
            if self._dead[slot]:
                props[slot] = empty
                continue
            if self._pos[slot] + steps + 1 >= self._t:
                # the draft context is exhausted before the target's is:
                # stop speculating this request, decode finishes it
                self._dead[slot] = True
                props[slot] = empty
                continue
            todo = list(req.tokens[int(self._ingested[slot]):])
            if todo:
                pending[slot] = todo
            else:
                props[slot] = empty
        while pending:
            n = int(engine.max_slots)
            pend = np.zeros((n, w), np.int32)
            plen = np.zeros(n, np.int32)
            posa = np.zeros(n, np.int32)
            batch = sorted(pending.items())
            for slot, toks in batch:
                m = min(len(toks), w)
                pend[slot, :m] = toks[:m]
                plen[slot] = m
                posa[slot] = self._pos[slot]
            args = (self.dspec, steps, self.dparams, jnp.asarray(pend),
                    jnp.asarray(plen), jnp.asarray(posa), self._kc,
                    self._vc)
            prog, entry = engine._program(
                "serving.spec_draft_propose", _draft_propose_step, 2, n,
                False, (k, self._fp), args)
            t0 = time.perf_counter()
            gs, self._kc, self._vc = prog(*args[2:])
            entry.observe(time.perf_counter() - t0)
            gs = np.asarray(jax.device_get(gs)).astype(np.int64)
            for slot, toks in batch:
                m = int(plen[slot])
                self._pos[slot] += m
                self._ingested[slot] += m
                rest = toks[m:]
                if rest:
                    # more emitted tokens than one window carries
                    # (defensive: ingest in rounds until caught up)
                    pending[slot] = rest
                else:
                    del pending[slot]
                    props[slot] = gs[slot, m - 1: m - 1 + k]
        return [props[slot] for slot in slots]

    def finish(self, slot):
        if self._bound:
            self._slot_rid[slot] = None
            self._dead[slot] = False


# ------------------------------- static single-program engine + spec

def _static_spec_prefill_impl(dspec, t_total, params, ids, true_len):
    """Prefill for the static speculative loop: full-prompt forward,
    K/V placed into a [L, B, t_total, Hkv, D] cache, and the first
    token taken greedily from the last REAL prompt position."""
    x, ks, vs = db.forward_sequence(params, ids, dspec)
    pad = t_total - ids.shape[1]
    kc = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    x_last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1,
                                          axis=1)[:, 0]
    tok0 = jnp.argmax(_logits(x_last, params, dspec),
                      axis=-1).astype(jnp.int32)
    return tok0, kc, vc


def _dense_verify_impl(dspec, params, toks, pos, kc, vc):
    """Greedy verification of C = K+1 candidate positions per row
    against the DENSE cache (the static engine's verify program — the
    paged analogue lives in inference/engine.py). Row b writes candidate
    K/V at positions pos[b] + [0, C) and attends each candidate under a
    `kv_pos <= q_pos` mask; rollback is, as everywhere, just the host
    not advancing pos past what it accepted — the next window's writes
    re-derive the same positions before any mask exposes them. Returns
    (greedy argmax [B, C] int32, kc, vc)."""
    b, c = toks.shape
    t = kc.shape[2]
    qpos = pos[:, None] + jnp.arange(c)[None, :]          # [B, C]
    wp = jnp.clip(qpos, 0, t - 1)
    x, rope = db.embed(                                   # [B, C, H]
        params, toks,
        jnp.clip(qpos, 0, db.num_positions(params, dspec) - 1), dspec)
    q_mask = jnp.arange(t)[None, None, :] <= qpos[:, :, None]  # [B,C,T]
    rows = jnp.arange(b)[:, None]

    def layer(xc, per_layer):
        lw, *kv = per_layer

        def attend(q, k, v):
            # the dense cache [B, T, Hkv, D] at [B, C] candidate positions
            kv[0] = kv[0].at[rows, wp].set(k.astype(kv[0].dtype))
            kv[1] = kv[1].at[rows, wp].set(v.astype(kv[1].dtype))
            return db.attend_many(q, *kv, q_mask)

        return db.block(xc, lw, dspec, attend, rope), tuple(kv)

    x, (kc, vc) = jax.lax.scan(layer, x, (params["layers"], kc, vc))
    lg = _logits(x.reshape(b * c, -1), params, dspec)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32).reshape(b, c)
    return greedy, kc, vc


_static_spec_prefill = functools.partial(
    jax.jit, static_argnums=(0, 1))(_static_spec_prefill_impl)
_dense_verify = functools.partial(
    jax.jit, static_argnums=(0,), donate_argnums=(4, 5))(
        _dense_verify_impl)


def generate_static_spec(model, ids, max_new_tokens, eos_token_id=None,
                         k=None, max_ngram=3):
    """Greedy speculative decoding on the STATIC engine: the n-gram
    proposer feeds a dense-cache verify program, so
    `Model.generate(engine="static", spec_decode="ngram")` multiplies
    tok/s by the acceptance rate without a serving engine. Outputs are
    token-identical to the non-speculative static engine (same
    emit-eos-forever padding contract: [B, max_new_tokens] int64, rows
    that finish early padded with eos).

    Every row rides every verify window — a row with no n-gram match
    proposes its last token repeated (auto-rejected, degenerating to a
    normal one-token decode step), so ONE program shape serves the
    whole generation and finished rows simply stop advancing."""
    from ..core.flags import flag
    from ..jit.api import default_buckets

    dspec, params = _spec_and_params(model)
    k = int(k if k is not None else flag("FLAGS_spec_k"))
    if k < 1:
        raise ValueError(f"speculation depth k must be >= 1, got {k}")
    ids = np.asarray(ids._data if hasattr(ids, "_data") else ids,
                     np.int64)
    if ids.ndim == 1:
        ids = ids[None]
    b, s = ids.shape
    mnt = int(max_new_tokens)
    eos = -1 if eos_token_id is None else int(eos_token_id)
    max_pos = int(db.num_positions(params, dspec))
    if s + mnt > max_pos:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({mnt}) exceeds "
            f"max_position_embeddings ({max_pos})")
    bucket = min(max(default_buckets(s), s), max_pos)
    t_total = bucket + mnt + k + 1
    ids_p = np.zeros((b, bucket), np.int32)
    ids_p[:, :s] = ids
    tok0, kc, vc = _static_spec_prefill(
        dspec, t_total, params, jnp.asarray(ids_p), jnp.int32(s))
    tok0 = np.asarray(jax.device_get(tok0))
    out = [[int(tok0[i])] for i in range(b)]
    pos = np.full(b, s, np.int32)
    last = tok0.astype(np.int64)
    done = np.array([mnt <= 1 or (eos >= 0 and int(tok0[i]) == eos)
                     for i in range(b)])
    # every window advances every unfinished row by >= 1 token
    for _ in range(b * mnt + 2):
        if done.all():
            break
        toks = np.zeros((b, k + 1), np.int32)
        props = np.zeros((b, k), np.int64)
        for i in range(b):
            p = propose_ngram(
                np.concatenate([ids[i], np.asarray(out[i], np.int64)]),
                k, max_ngram)
            if p.size < k:
                p = np.concatenate(
                    [p, np.full(k - p.size, int(last[i]), np.int64)])
            props[i] = p
            toks[i, 0] = last[i]
            toks[i, 1:] = p
        g, kc, vc = _dense_verify(dspec, params, jnp.asarray(toks),
                                  jnp.asarray(pos), kc, vc)
        g = np.asarray(jax.device_get(g))
        for i in range(b):
            if done[i]:
                continue
            a = 0
            while a < k and props[i][a] == g[i, a]:
                a += 1
            new = [int(x) for x in props[i][:a]] + [int(g[i, a])]
            new = new[: mnt - len(out[i])]
            if eos >= 0:
                for j, tkn in enumerate(new):
                    if tkn == eos:
                        new = new[: j + 1]
                        break
            out[i].extend(new)
            pos[i] += len(new)
            last[i] = new[-1]
            if (eos >= 0 and new[-1] == eos) or len(out[i]) >= mnt:
                done[i] = True
    res = np.full((b, mnt), eos if eos >= 0 else 0, np.int64)
    for i in range(b):
        row = out[i][:mnt]
        res[i, :len(row)] = row
    return res

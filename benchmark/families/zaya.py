"""ZAYA1 (Zyphra, 2025-11; the 8B reasoning model 2026-05): the program's
model from the published ``config.json`` keys, a plain reference forward
pass, and what the ``serve_lm`` runner asks a family for.

Every layer runs attention in a compressed latent (1024 channels of
queries, 256 of keys, 256 of values for a stream of 2048) whose queries
and keys are convolved over the two tokens before and half of whose
values are the token before's, then a top-1 routed expert layer whose
router adds the same token's router state of the layer before.

The reference follows the equations of ``models/latent_conv_moe_lm.py``'s
docstring (ISSUE 35) and nothing of the program: float32 ``jax.numpy``,
no kernel, no cache, no tails, no chunks, no batching; the convs and the
value shift as shifts of the whole sequence, dense causal scores, the
experts as a dense sum over a one-hot choice. It reads the program's
parameter tree and shares no code with it. At the cell's sizes it works
in blocks (queries ``query_block`` at a time, one matrix cast to float32
at a time, one expert at a time, the vocabulary in pieces, the logits of
the rows asked for only, written into one buffer) so that it fits beside
the served weights and the pages. Call it under
``jax.default_matmul_precision("highest")``.

One thing the runner's comparison needs beyond the plain pass: with ONE
expert a token, a token whose two best experts tie within bf16's rounding
takes either, and from there on it is another token's stream (forced to
the program's own experts the reference reads a mean shortfall of 0.0005
logits where the plain pass reads 0.03: CPU, published widths). The
runner hands the reference the program's tokens and not its experts, so
:func:`follow_routing` reads them off the tokens, at ties only.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
from jax.scipy.special import erf

#: the kernels whose dispatches decide ``correct``: each must have run on
#: its Pallas body and never on its ``lax`` form
KERNELS = ("ragged_paged_prefill", "ragged_paged_decode", "moe_grouped_ffn")

#: :func:`follow_routing`: two experts tie for a token where the float32
#: router's two best scores (p + beta) lie this close (the bf16 program's
#: own score gap differs from the float32 one by about 0.004 in the first
#: layers and 0.02 after a few, with a long tail: CPU readings at the
#: published widths, PERF.md section 6); a switch to the runner-up is
#: kept where the chosen token then falls at most ``ROUTING_EXPLAINED``
#: logits short (tokens whose experts all match fall 0.0005 short on
#: average and 0.03 at worst); ``ROUTING_ROUNDS`` trials a row. On the
#: chip 0.04 / 0.1 left a third of the plain reading and 0.02 / 0.05 a
#: little more (my chip run, PR 35, chiprun_out/calib35.jsonl)
ROUTING_TIE = 0.04
ROUTING_EXPLAINED = 0.1
ROUTING_ROUNDS = 3

#: the published keys the program's config takes under the same name
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "max_position_embeddings", "partial_rotary_factor",
         "cca_time0", "cca_time1", "num_experts", "num_experts_per_tok",
         "moe_intermediate_size", "router_hidden_size")


def _rope_theta(sizes: dict) -> float:
    """Every layer of this row is of type ``hybrid`` (no sliding
    window), so one theta serves the stage."""
    kinds = set(sizes["layer_types"][:sizes["num_hidden_layers"]])
    if kinds != {"hybrid"} or sizes.get("sliding_window") is not None:
        raise ValueError("the program is written for layers of type "
                         "'hybrid' without a sliding window")
    return float(sizes["rope_parameters"]["hybrid"]["rope_theta"])


def model_config(sizes: dict, **kw):
    from paddle_tpu.models.latent_conv_moe_lm import LatentConvMoELMConfig
    for flag, must in (("attention_bias", False), ("lm_head_bias", False),
                       ("tie_word_embeddings", True),
                       ("hidden_act", "silu")):
        if sizes.get(flag, must) != must:
            raise ValueError(f"the program is written for {flag}={must!r}")
    given = {k: sizes[k] for k in _KEYS if k in sizes}
    return LatentConvMoELMConfig(rope_theta=_rope_theta(sizes), **given,
                                 **kw)


def sizes_of(cfg) -> dict:
    """The published keys the reference reads, from a program config
    (:func:`model_config` the other way round)."""
    sizes = {k: getattr(cfg, k) for k in _KEYS}
    sizes.update(
        layer_types=["hybrid"] * cfg.num_hidden_layers, sliding_window=None,
        rope_parameters={"hybrid": {
            "rope_theta": cfg.rope_theta,
            "partial_rotary_factor": cfg.partial_rotary_factor}})
    return sizes


def build(sizes: dict, *, interpret: bool = False):
    """The program's model for the published ``sizes``."""
    from paddle_tpu.models.latent_conv_moe_lm import LatentConvMoELM
    return LatentConvMoELM(model_config(
        sizes, kernel_impl="pallas_interpret" if interpret else "pallas"))


def positions(sizes: dict) -> int:
    return sizes["max_position_embeddings"]


def vocabulary(sizes: dict) -> int:
    return sizes["vocab_size"]


# -- the plain reference ------------------------------------------------------

def _f32(a):
    return a.astype(jnp.float32)


def _rms(u, g, eps):
    return g * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps)


def _silu(u):
    return u / (1.0 + jnp.exp(-u))


def _gelu(u):
    return 0.5 * u * (1.0 + erf(u / math.sqrt(2.0)))


def _before(a):
    """Row ``t`` holds row ``t - 1``; row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]])


def _rope(u, pos, theta, rotary):
    """The first ``rotary`` entries of each head rotated, pairing ``(i, i
    + rotary/2)``; ``u`` (N, heads, d), ``pos`` (N,)."""
    half = rotary // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary)
    ang = (pos.astype(jnp.float32)[:, None] * freq)[:, None, :]
    lo, hi, rest = u[..., :half], u[..., half:rotary], u[..., rotary:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang), rest], -1)


def _pieces(n: int, limit: int) -> int:
    """The fewest equal pieces of ``n`` of at most ``limit`` each."""
    return next(k for k in range(1, n + 1) if n % k == 0 and n // k <= limit)


def reference_hidden(params, ids, sizes: dict, query_block: int = 256,
                     forced=None):
    """(N,) ids -> (N, D) float32 residual stream after the last layer,
    and the routing it took: ``pick`` (L, N) the expert of every token
    and layer, ``second`` the runner-up, ``gap`` the runner-up's distance
    in ``p + beta``. ``forced`` (L, N) int32: the expert a token takes in
    a layer whatever its router says, -1 where the router decides."""
    n = ids.shape[0]
    h, g, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
               sizes["head_dim"])
    group = h // g
    eps, theta = sizes["rms_norm_eps"], _rope_theta(sizes)
    rotary = int(d * sizes["partial_rotary_factor"])
    pos = jnp.arange(n)
    nq = _pieces(n, query_block)
    x = _f32(params["embed"]["weight"][ids])
    r_before = None
    route = []
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731
        u = _rms(x, _f32(lp["attn_norm"]["scale"]), eps)
        z = jnp.concatenate([u @ w("q_proj"), u @ w("k_proj")], -1)
        w0 = w("conv0")
        c = _f32(lp["conv0"]["bias"]) + w0[:, 0] * _before(z) + w0[:, 1] * z
        w1 = w("conv1")                                     # (2, H+G, d, d)
        per_head = lambda a: a.reshape(n, h + g, d)          # noqa: E731
        s = _f32(lp["conv1"]["bias"]) + (
            jnp.einsum("nhi,hio->nho", per_head(_before(c)), w1[0])
            + jnp.einsum("nhi,hio->nho", per_head(c), w1[1])).reshape(n, -1)
        zq, zk = z[:, :h * d].reshape(n, h, d), z[:, h * d:].reshape(n, g, d)
        sq, sk = s[:, :h * d].reshape(n, h, d), s[:, h * d:].reshape(n, g, d)
        q = sq + 0.5 * (zq + jnp.repeat(zk, group, axis=1))
        k = sk + 0.5 * (zq.reshape(n, g, group, d).mean(2) + zk)
        norm = lambda a: jnp.sqrt(jnp.sum(a * a, -1, keepdims=True))  # noqa
        q = math.sqrt(d) * q / norm(q)
        k = math.sqrt(d) * _f32(lp["temperature"])[:, None] * k / norm(k)
        q, k = _rope(q, pos, theta, rotary), _rope(k, pos, theta, rotary)
        v = jnp.concatenate([u @ w("v_proj"),
                             _before(u @ w("v_shift_proj"))],
                            -1).reshape(n, g, d)
        kk = jnp.repeat(k, group, axis=1)       # query head j reads j // 4
        vv = jnp.repeat(v, group, axis=1)

        def attend(block, kk=kk, vv=vv):
            qh, p = block                                     # a query block
            sc = jnp.einsum("qhd,nhd->hqn", qh, kk) / math.sqrt(d)
            sc = jnp.where((pos[None, :] <= p[:, None])[None], sc, -jnp.inf)
            return jnp.einsum("hqn,nhd->qhd", jax.nn.softmax(sc, -1), vv)

        att = jax.lax.map(attend, (q.reshape(nq, n // nq, h, d),
                                   pos.reshape(nq, n // nq))
                          ).reshape(n, h * d)
        res = lp["attn_residual"]
        x = _f32(res["keep"]) * x + _f32(res["add"]) * (att @ w("o_proj"))

        rp = lp["router"]
        t = _rms(x, _f32(lp["ffn_norm"]["scale"]), eps)
        r = t @ _f32(rp["in_proj"]["weight"]) + _f32(rp["in_proj"]["bias"])
        if r_before is not None:
            r = r + _f32(rp["carry_scale"]) * r_before
        r_before = r
        hid = _rms(r, _f32(rp["norm"]["scale"]), eps)
        hid = _gelu(_gelu(hid @ _f32(rp["fc1"]["weight"]))
                    @ _f32(rp["fc2"]["weight"]))
        p = jax.nn.softmax(hid @ _f32(rp["out_proj"]["weight"]), -1)
        score = p + _f32(rp["balance_bias"])
        best = jnp.argmax(score, -1)                         # ties: lower e
        experts = jnp.arange(p.shape[1])
        rest = jnp.where(best[:, None] == experts, -jnp.inf, score)
        second = jnp.argmax(rest, -1)
        gap = jnp.max(score, -1) - jnp.max(rest, -1)
        pick = best if forced is None else jnp.where(
            forced[i] >= 0, forced[i], best)
        route.append((pick, jnp.where(pick == best, second, best), gap))
        coef = jnp.where(pick[:, None] == experts, p, 0.0)

        def expert(y, ew, t=t):
            c_e, g_w, u_w, d_w = ew                           # one expert
            hidden = _silu(t @ _f32(g_w).T) * (t @ _f32(u_w).T)
            return y + c_e[:, None] * (hidden @ _f32(d_w)), None

        ex = lp["experts"]
        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (coef.T, ex["gate"], ex["up"], ex["down"]))
        res = lp["ffn_residual"]
        x = _f32(res["keep"]) * x + _f32(res["add"]) * y
    pick, second, gap = (jnp.stack(a) for a in zip(*route))
    return x, {"pick": pick, "second": second, "gap": gap}


def _head_pieces(params, x, sizes, vocab_block):
    """The final norm of ``x`` and the tied head a piece at a time:
    (normed x, pieces, width of a piece, piece ``i`` of the head in
    float32)."""
    x = _rms(x, _f32(params["final_norm"]["scale"]), sizes["rms_norm_eps"])
    head = params["embed"]["weight"]
    k = _pieces(head.shape[0], vocab_block)
    width = head.shape[0] // k
    return x, k, width, lambda i: _f32(
        jax.lax.dynamic_slice_in_dim(head, i * width, width, 0))


def _shortfall(params, x, chosen, sizes, vocab_block):
    """(rows, D) stream, (rows,) the token chosen after each row ->
    (rows,) the best logit less the chosen token's, the vocabulary a
    piece at a time and no logits kept."""
    x, k, _width, piece = _head_pieces(params, x, sizes, vocab_block)
    took = jnp.sum(x * _f32(params["embed"]["weight"][chosen]), -1)
    best = jax.lax.fori_loop(
        0, k, lambda i, m: jnp.maximum(m, jnp.max(x @ piece(i).T, -1)),
        jnp.full(x.shape[:1], -jnp.inf))
    return jnp.maximum(best - took, 0.0)


def follow_routing(params, ids, sizes: dict, lo, rows: int,
                   query_block: int = 256, vocab_block: int = 8192,
                   tie=None, explained=None, rounds=None, chosen=None):
    """The routing of the reference's pass over ``ids``, with the
    program's own expert where the reference cannot decide one.

    One expert a token is a step function: where the float32 router's two
    best scores lie within the program's rounding of each other (a
    **tie**: ``gap < tie``), the bf16 program takes either, and from that
    layer on that token's stream, its K, V and tails are another's. The
    runner hands the reference the program's tokens, not its experts, so
    the reference reads them off the tokens: a row ``lo <= t < lo +
    rows`` whose chosen token (``ids[t + 1]``) falls more than
    ``explained`` logits short of the reference's best, and whose routing
    has a tie, is tried with the tied layer's runner-up (the closest tie
    first, one a round); the switch is kept where it **explains** the row
    (the shortfall falls to ``explained`` or less) and taken back, for
    good, where it does not. Switches are kept only at ties, so a program
    whose stream is off by more than rounding is not excused: its rows
    stay short whichever tied expert the reference takes (the float8 and
    zeroed-tail controls in the configuration file).

    Returns (forced (L, N) int32 for :func:`reference_hidden`, counts
    (4,) float32: rows with a tie, rows tried, switches kept, rows still
    short at the end of the rounds)."""
    tie = ROUTING_TIE if tie is None else tie
    explained = ROUTING_EXPLAINED if explained is None else explained
    rounds = ROUTING_ROUNDS if rounds is None else rounds
    n, layers = ids.shape[0], sizes["num_hidden_layers"]
    at = lo + jnp.arange(rows)
    if chosen is None:
        chosen = ids[jnp.minimum(at + 1, n - 1)]
    in_rows = jnp.zeros((n,), bool).at[at].set(True)

    def short_of(x):
        s = _shortfall(params, jax.lax.dynamic_slice_in_dim(x, lo, rows, 0),
                       chosen, sizes, vocab_block)
        return jnp.zeros((n,), jnp.float32).at[at].set(s)

    def one_round(state, _):
        forced, tried, last = state         # tried: (N,) layer or -1
        x, route = reference_hidden(params, ids, sizes, query_block, forced)
        short = short_of(x)
        # judge the last round's trials: kept where they explain the row,
        # else the router's own expert again, fixed
        layer = jnp.arange(layers)[:, None]
        was_tried = (tried[None, :] == layer)
        failed = was_tried & (short > explained)[None, :]
        forced = jnp.where(failed, route["second"], forced)
        stale = failed.any(0)               # this pass ran under the trial
        short = jnp.where(stale, last, short)
        # try the closest open tie of every row that is still short
        open_tie = (route["gap"] < tie) & (forced < 0) & in_rows[None, :]
        want = (short > explained) & ~stale & open_tie.any(0)
        closest = jnp.argmin(jnp.where(open_tie, route["gap"], jnp.inf), 0)
        trial = want[None, :] & (layer == closest[None, :])
        forced = jnp.where(trial, route["second"], forced)
        kept = (was_tried & ~failed).sum()
        return ((forced, jnp.where(want, closest, -1), short),
                jnp.stack([open_tie.any(0).sum(), want.sum(), kept,
                           (short > explained).sum()]).astype(jnp.float32))

    start = (jnp.full((layers, n), -1, jnp.int32),
             jnp.full((n,), -1, jnp.int32), jnp.zeros((n,), jnp.float32))
    (forced, tried, last), seen = jax.lax.scan(one_round, start, None,
                                               length=rounds + 1)
    # the last round's trials have not been judged: they are dropped
    layer = jnp.arange(layers)[:, None]
    forced = jnp.where(tried[None, :] == layer, -1, forced)
    return forced, jnp.stack([seen[0, 0], seen[:, 1].sum() - seen[-1, 1],
                              seen[:, 2].sum(), seen[-1, 3]])


def reference_logits(params, ids, sizes: dict, lo=0, rows=None,
                     query_block: int = 256, vocab_block: int = 8192,
                     probe=None, follow: bool = True):
    """(1, N) ids -> (1, rows, V) float32 logits of positions ``lo ..
    lo + rows`` (all of them by default; ``lo`` may be traced), the
    vocabulary ``vocab_block`` rows of the tied embedding at a time into
    one buffer. ``follow``: with the program's expert where the router
    ties (:func:`follow_routing`, reading ``ids`` as the program's own
    tokens); without, the router's own expert everywhere. With ``probe``
    (what ``serve_lm`` passes every family): (logits, selections), the
    selections empty: this family's attention selects nothing and the
    runner reads none."""
    ids = ids[0]
    rows = ids.shape[0] if rows is None else rows
    forced = None
    if follow:
        forced, counts = follow_routing(params, ids, sizes, lo, rows,
                                        query_block, vocab_block)
        jax.debug.callback(_say_routing, counts, rows)
    x, _ = reference_hidden(params, ids, sizes, query_block, forced)
    x = jax.lax.dynamic_slice_in_dim(x, lo, rows, axis=0)
    x, k, width, piece = _head_pieces(params, x, sizes, vocab_block)

    def write(i, logits):
        return jax.lax.dynamic_update_slice_in_dim(
            logits, x @ piece(i).T, i * width, axis=1)

    logits = jax.lax.fori_loop(
        0, k, write, jnp.zeros((rows, k * width), jnp.float32))[None]
    return logits if probe is None else (logits,
                                         jnp.zeros((0,), jnp.bool_))


def _say_routing(counts, rows):
    tied, tried, kept, short = (int(c) for c in counts)
    print(f"[bench] reference routing: of {rows} rows (the request's and "
          f"the padding after it) {tied} have a layer whose two best "
          f"experts tie within {ROUTING_TIE}; {tried} trials of the "
          f"runner-up, {kept} kept (they explain the row to "
          f"{ROUTING_EXPLAINED} logits); {short} rows still short",
          file=sys.stderr, flush=True)


# -- what the traced window's kernels had to do -------------------------------

def kernel_needs(sizes: dict, itemsize: int, layers: int, traced: dict,
                 live_token_steps: float, selected_token_steps: float) -> dict:
    """Nominal operations and bytes of the grouped expert kernel and the
    dense paged decode kernel at this family's shapes over the traced
    part of the window. ``traced``: the program's counters over that
    part (they already count layers); ``live_token_steps``: summed over
    every decode token step of every slot, the tokens cached (the
    driver's count).

    - experts: every touched expert's three matrices read once a layer
      and call, 6 D F operations a token-expert pair (one pair a token);
    - paged decode: the K and V rows of every cached token, 2 KV heads of
      128 each, a layer and token step."""
    del selected_token_steps
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    kv, dh = sizes["num_key_value_heads"], sizes["head_dim"]
    touched = traced.get("serving_moe_experts_touched_total", 0.0)
    pairs = traced.get("serving_moe_assignments_total", 0.0)
    return {
        "moe_ffn_needed_bytes": touched * 3 * d * f * itemsize,
        "moe_ffn_needed_flops": pairs * 6.0 * d * f,
        "paged_decode_needed_bytes": live_token_steps * layers * 2 * kv
        * dh * itemsize,
    }

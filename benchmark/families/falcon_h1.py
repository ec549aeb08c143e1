"""Falcon-H1 (TII, 2025-05): the program's model from the published
``config.json`` keys, a plain reference forward pass, and what the
``serve_lm`` runner asks a family for.

Every block runs grouped-query attention and a Mamba-2 mixer side by side
on one normed input and adds both to the residual stream, then a SwiGLU
MLP; the published multipliers scale the embedding, the attention input,
the keys, each part of the mixer's input projection, both branches'
outputs, the MLP's gate and output, and the logits.

The reference follows the equations of PERF.md section 4 and nothing of
the program: float32 ``jax.numpy``, no kernel, no cache, no chunks, no
batching; dense causal scores; the recurrence as a ``lax.scan`` over
tokens from a zero state, with the state held ``(heads, head_dim,
state)``. It reads the program's parameter tree and shares no code with
it. At the cell's sizes it works in blocks (queries ``query_block`` at a
time, one matrix cast to float32 at a time, the MLP's hidden units and the
vocabulary in pieces, the logits of the rows asked for only, written into
one buffer) so that it fits beside the served weights, the state pool and
the pages. Call it under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the kernels whose dispatches decide ``correct``: each must have run on
#: its Pallas body and never on its ``lax`` form
KERNELS = ("ragged_paged_prefill", "ragged_paged_decode", "ssd_chunk_scan",
           "ssm_decode_update")

#: what the benchmark's seeded weights draw the heads' decay rates ``A``
#: from (``configs/falcon_h1_34b.json``, ``assumed.time_scales``): a
#: sixteenth of the Mamba-2 module's own 1..16. A head's state carries
#: about 0.4 / A of the mixer's output beside the ``D`` skip, and a head
#: remembers 1 / (A dt) tokens: with the module's draw the state is 7% of
#: the output, one head in forty is slow enough to stall in bfloat16, and
#: a bfloat16 state pool read a mean shortfall of 9.5e-7 logits where
#: sound runs read 0.9e-7 to 3.8e-7 (my chip runs, PR 32): no limit
#: between them holds on every seed
A_INIT_RANGE = (0.0625, 1.0)

#: the published keys the program's config takes under the same name
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "intermediate_size", "rms_norm_eps", "max_position_embeddings",
         "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
         "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
         "mamba_conv_bias", "mamba_rms_norm", "mamba_norm_before_gate",
         "embedding_multiplier", "lm_head_multiplier",
         "attention_in_multiplier", "attention_out_multiplier",
         "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")


def model_config(sizes: dict, **kw):
    from paddle_tpu.models.hybrid_ssm_lm import HybridSSMLMConfig
    for flag, must in (("attention_bias", False), ("mlp_bias", False),
                       ("mamba_proj_bias", False), ("projectors_bias", False),
                       ("mamba_use_mlp", True),
                       ("tie_word_embeddings", False), ("rope_scaling", None),
                       ("hidden_act", "silu")):
        if sizes.get(flag, must) != must:
            raise ValueError(f"the program is written for {flag}={must!r}")
    given = {k: sizes[k] for k in _KEYS if k in sizes}
    for k in ("ssm_multipliers", "mlp_multipliers"):
        if k in sizes:
            given[k] = tuple(sizes[k])
    if "rope_theta" in sizes:
        given["rope_theta"] = float(sizes["rope_theta"])
    # not a published key: a control run's state type (configs' files
    # leave it out, and the state is float32)
    if "state_dtype" in sizes:
        given["state_dtype"] = sizes["state_dtype"]
    return HybridSSMLMConfig(**given, **kw)


def sizes_of(cfg) -> dict:
    """The published keys the reference reads, from a program config
    (:func:`model_config` the other way round)."""
    sizes = {k: getattr(cfg, k) for k in _KEYS}
    sizes.update(rope_theta=cfg.rope_theta,
                 ssm_multipliers=list(cfg.ssm_multipliers),
                 mlp_multipliers=list(cfg.mlp_multipliers))
    return sizes


def build(sizes: dict, *, interpret: bool = False):
    """The program's model for the published ``sizes``."""
    from paddle_tpu.models.hybrid_ssm_lm import HybridSSMLM
    return HybridSSMLM(model_config(
        sizes, a_init_range=A_INIT_RANGE,
        kernel_impl="pallas_interpret" if interpret else "pallas"))


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


def _rope(u, pos, theta):
    """Rotate-half pairing ``(i, i + d/2)`` over the whole last axis;
    ``u`` (N, heads, d), ``pos`` (N,)."""
    d = u.shape[-1]
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = (pos.astype(jnp.float32)[:, None] * freq[None, :])[:, None, :]
    lo, hi = u[..., :d // 2], u[..., d // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


def _pieces(n: int, limit: int) -> int:
    """The fewest equal pieces of ``n`` of at most ``limit`` each."""
    return next(k for k in range(1, n + 1) if n % k == 0 and n // k <= limit)


def _mixer(lp, u, sizes):
    """(N, D) normed block input -> (N, D): the Mamba-2 mixer over the
    whole sequence from a zero state, token by token."""
    n = u.shape[0]
    hm, p, g, ns = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
                    sizes["mamba_n_groups"], sizes["mamba_d_state"])
    d_ssm, taps = sizes["mamba_d_ssm"], sizes["mamba_d_conv"]
    m = sizes["ssm_multipliers"]
    proj = (u * sizes["ssm_in_multiplier"]) @ _f32(lp["in_proj"]["weight"])
    cut = (d_ssm, 2 * d_ssm, 2 * d_ssm + g * ns, 2 * d_ssm + 2 * g * ns)
    z = proj[:, :cut[0]] * m[0]
    xbc = jnp.concatenate([proj[:, cut[0]:cut[1]] * m[1],
                           proj[:, cut[1]:cut[2]] * m[2],
                           proj[:, cut[2]:cut[3]] * m[3]], -1)
    dt = proj[:, cut[3]:] * m[4]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    w = _f32(lp["conv"]["weight"])                          # (channels, taps)
    conv = _silu(_f32(lp["conv"]["bias"]) + sum(
        w[:, j] * padded[j:j + n] for j in range(taps)))
    x = conv[:, :d_ssm].reshape(n, hm, p)
    bm = jnp.repeat(conv[:, d_ssm:d_ssm + g * ns].reshape(n, g, ns),
                    hm // g, axis=1)                        # head h: h // 16
    cm = jnp.repeat(conv[:, d_ssm + g * ns:].reshape(n, g, ns),
                    hm // g, axis=1)
    dt = jnp.log1p(jnp.exp(dt + lp["dt_bias"]))             # softplus
    decay = jnp.exp(-dt * jnp.exp(lp["A_log"]))             # (N, H)

    def token(state, t):
        a_t, dt_t, x_t, b_t, c_t = t
        state = a_t[:, None, None] * state + (
            dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]    # (H,P,Ns)
        return state, jnp.sum(state * c_t[:, None, :], -1)

    _, y = jax.lax.scan(token, jnp.zeros((hm, p, ns), jnp.float32),
                        (decay, dt, x, bm, cm))
    y = (y + lp["D"][None, :, None] * x).reshape(n, d_ssm) * _silu(z)
    y = _rms(y.reshape(n, g, -1), _f32(lp["mixer_norm"]["scale"]).reshape(
        g, -1), sizes["rms_norm_eps"]).reshape(n, d_ssm)
    return (y @ _f32(lp["out_proj"]["weight"])) * sizes["ssm_out_multiplier"]


def _mlp(lp, b, sizes, hidden_block):
    """SwiGLU with the published multipliers, ``hidden_block`` hidden
    units at a time: one slice of each of the three matrices in float32."""
    f = sizes["intermediate_size"]
    m = sizes["mlp_multipliers"]
    k = _pieces(f, hidden_block)
    width = f // k

    def piece(i, y):
        cols = lambda w: _f32(jax.lax.dynamic_slice_in_dim(      # noqa: E731
            w, i * width, width, axis=1))
        hidden = (b @ cols(lp["up_proj"]["weight"])) * _silu(
            (b @ cols(lp["gate_proj"]["weight"])) * m[0])
        return y + hidden @ _f32(jax.lax.dynamic_slice_in_dim(
            lp["down_proj"]["weight"], i * width, width, axis=0))

    return jax.lax.fori_loop(0, k, piece, jnp.zeros_like(b)) * m[1]


def reference_hidden(params, ids, sizes: dict, query_block: int = 256,
                     hidden_block: int = 5376):
    """(N,) ids -> (N, D) float32 residual stream after the last layer."""
    n = ids.shape[0]
    h, kv, dh = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    pos = jnp.arange(n)
    nq = _pieces(n, query_block)
    x = _f32(params["embed"]["weight"][ids]) * sizes["embedding_multiplier"]
    for i in range(sizes["num_hidden_layers"]):
        lp = params["layers"][str(i)]
        w = lambda name: _f32(lp[name]["weight"])            # noqa: E731
        u = _rms(x, _f32(lp["input_norm"]["scale"]), eps)
        a = u * sizes["attention_in_multiplier"]
        q = _rope((a @ w("q_proj")).reshape(n, h, dh), pos, theta)
        k = _rope(((a @ w("k_proj")) * sizes["key_multiplier"]).reshape(
            n, kv, dh), pos, theta)
        v = (a @ w("v_proj")).reshape(n, kv, dh)
        kk = jnp.repeat(k, h // kv, axis=1)     # query head j reads j // 5
        vv = jnp.repeat(v, h // kv, axis=1)

        def attend(block, kk=kk, vv=vv):
            qh, p = block                                     # a query block
            s = jnp.einsum("qhd,nhd->hqn", qh, kk) / jnp.sqrt(float(dh))
            s = jnp.where((pos[None, :] <= p[:, None])[None], s, -jnp.inf)
            return jnp.einsum("hqn,nhd->qhd", jax.nn.softmax(s, -1), vv)

        o = jax.lax.map(attend, (q.reshape(nq, n // nq, h, dh),
                                 pos.reshape(nq, n // nq))).reshape(n, h * dh)
        att = (o @ w("o_proj")) * sizes["attention_out_multiplier"]
        x = x + att + _mixer(lp, u, sizes)
        x = x + _mlp(lp, _rms(x, _f32(lp["ff_norm"]["scale"]), eps), sizes,
                     hidden_block)
    return x


def reference_logits(params, ids, sizes: dict, lo=0, rows=None,
                     query_block: int = 256, vocab_block: int = 8192,
                     probe=None):
    """(1, N) ids -> (1, rows, V) float32 logits of positions ``lo ..
    lo + rows`` (all of them by default; ``lo`` may be traced), the
    vocabulary ``vocab_block`` rows of the head at a time into one
    buffer. With ``probe`` (what ``serve_lm`` passes every family):
    (logits, selections), the selections empty: this family's attention
    selects nothing and the runner reads none."""
    x = reference_hidden(params, ids[0], sizes, query_block)
    rows = x.shape[0] if rows is None else rows
    x = jax.lax.dynamic_slice_in_dim(x, lo, rows, axis=0)
    x = _rms(x, _f32(params["final_norm"]["scale"]), sizes["rms_norm_eps"])
    head = params["head"]["weight"]
    v = head.shape[0]
    k = _pieces(v, vocab_block)
    width = v // k

    def piece(i, logits):
        wp = _f32(jax.lax.dynamic_slice_in_dim(head, i * width, width, 0))
        return jax.lax.dynamic_update_slice_in_dim(
            logits, x @ wp.T, i * width, axis=1)

    logits = jax.lax.fori_loop(
        0, k, piece, jnp.zeros((rows, v), jnp.float32))
    logits = (logits * sizes["lm_head_multiplier"])[None]
    return logits if probe is None else (logits,
                                         jnp.zeros((0,), jnp.bool_))


# -- what the traced window's kernels had to do -------------------------------

def kernel_needs(sizes: dict, itemsize: int, layers: int, traced: dict,
                 live_token_steps: float, selected_token_steps: float) -> dict:
    """Nominal operations and bytes of the two state-space kernels over
    the traced part of the window, from the program's counters over that
    part (both already count layers): what the recurrence needs, whatever
    computes it.

    - a token of a head: ``S = a S + dt x (x) B`` is 3 operations a state
      element, ``y = S C`` 2 more: 5 H P N; it reads ``x`` and writes
      ``y`` (H P each), reads ``B``, ``C`` (G N each) and ``dt`` (H),
      float32;
    - decode: every live slot's state tiles (H P N float32) read once and
      written once a token step;
    - scan: the tokens' rows as above, and the state tiles the prefill
      calls read and wrote: the engine's state bytes less the decode
      steps' share (a lane reads its tiles once a chunk unless its prompt
      starts there, and writes them once)."""
    del itemsize, layers, live_token_steps, selected_token_steps
    h, p, n, g = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
                  sizes["mamba_d_state"], sizes["mamba_n_groups"])
    tiles = 4.0 * h * p * n
    window = 4.0 * (sizes["mamba_d_conv"] - 1) * (h * p + 2 * g * n)
    token_rows = 4.0 * (2 * h * p + 2 * g * n + h)
    token_flops = 5.0 * h * p * n
    steps = traced.get("serving_ssm_decode_slot_steps_total", 0.0)
    tokens = traced.get("serving_ssm_prefill_tokens_total", 0.0)
    moved = sum(v for k, v in traced.items()
                if k.startswith("serving_ssm_state_bytes_total"))
    # the engine counts a slot's whole state (tiles and conv window); the
    # window is the conv's, which XLA computes
    prefill_tiles = max(moved - 2.0 * steps * (tiles + window), 0.0) \
        * tiles / (tiles + window)
    return {
        "ssm_decode_needed_bytes": steps * (2.0 * tiles + token_rows),
        "ssm_decode_needed_flops": steps * token_flops,
        "ssd_scan_needed_bytes": tokens * token_rows + prefill_tiles,
        "ssd_scan_needed_flops": tokens * token_flops,
    }

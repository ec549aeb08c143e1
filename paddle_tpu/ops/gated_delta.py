"""Gated delta rule (Gated DeltaNet's recurrence) over a pool of per-slot
states: a chunked scan for a prompt chunk, a one-token update for a decode
step.

Per value head ``i`` with a state ``S_i`` of ``dk x dv`` (key head ``i //
r`` gives its ``q`` and ``k``, ``r`` value heads a key head), token ``t``::

    S <- exp(g_t) S                       g_t <= 0, the head's log decay
    d  = beta_t (v_t - S^T k_t)           what the state does not answer yet
    S <- S + k_t (x) d
    o_t = S^T q_t

(``q`` and ``k`` come normalised, ``q`` scaled: the model's). The update
SUBTRACTS what the state already answers for the key, so it is no
cumulative sum as the state-space recurrence of :mod:`ssm_scan` is: inside
a tile of ``L`` tokens the ``d`` of a token depends on the ``d`` of every
token before it, a unit lower-triangular system. A token whose ``beta`` and
``g`` are 0 changes nothing: that is how a chunk's pad positions are left
out.

The states live in a pool ``(R, Hv, dk, dv)`` float32, one row a serving
slot, row 0 the null row pad lanes and dead slots point at. A head's tile
is kept key-major, ``(dk, dv)``: a token's ``v``, ``d`` and ``o`` are rows
along the tile's lanes, ``S^T k`` is a sum over sublanes. Both kernels take
the pool and the pool row of every lane, and hand the pool back updated in
place (``input_output_aliases``): a tile is read once and written once.

``gated_delta_chunk_scan``: grid ``(lanes, value heads, C / L)`` over
tiles of ``L = min(C, 64)`` tokens (the published chunk), the WY form of
the recurrence inside a tile. With ``c_t`` the running sum of ``g`` inside
the tile and ``A[t, s] = beta_t (k_t . k_s) exp(c_t - c_s)`` for ``s < t``::

    T    = (I + A)^-1                      forward substitution, by blocks
    u    = T (beta v)       w = T (beta k exp(c))
    d    = u - w S_start                   every token's correction at once
    o    = (q exp(c)) S_start + ((q k^T) * decay[t >= s]) d
    S    = exp(c_L) S_start + (k exp(c_L - c))^T d

``T``, ``u``, ``w`` and the decay factors do not depend on the state and
are prepared by XLA (small products over arrays of the tokens' size); the
four products with the carried state are the kernel's. ``fresh`` lanes
start from a zero state whatever their row holds (a slot's reset at
admission).

``gated_delta_decode_update``: grid ``(slots, head blocks)``, one token a
slot, on the VPU: about 7 operations an element of a tile read once and
written once. A slot whose row is 0 is skipped: nothing of its tile is
computed and the null row is written back as it was read.

State and decay arithmetic is float32; a float32 dot says ``HIGHEST``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST

#: tokens of one tile of the chunked scan (the published chunk size)
DELTA_TILE = 64
#: bytes of state tiles one decode grid step holds, in and out each
_DECODE_BLOCK_BYTES = 2 << 20


def _tile(c: int) -> int:
    if c <= DELTA_TILE:
        return c
    if c % DELTA_TILE:
        raise ValueError(f"a chunk of {c} tokens is no multiple of the "
                         f"delta rule's tile of {DELTA_TILE}")
    return DELTA_TILE


#: rows of a diagonal block of the forward substitution
_SOLVE_BLOCK = 16


def _rows_inverse(a):
    """``(I + a)^-1`` for ``a`` (..., n, n) strictly lower triangular, by
    forward substitution: row ``i`` of the inverse from the rows before
    it (the published chunked form's loop), ``n - 1`` steps in a row."""
    n = a.shape[-1]

    def row(i, m):
        r = jax.lax.dynamic_slice_in_dim(m, i, 1, axis=-2)
        r = r + jnp.einsum("...ij,...jk->...ik", r, m, precision=_HI)
        return jax.lax.dynamic_update_slice_in_dim(m, r, i, axis=-2)

    return jax.lax.fori_loop(1, n, row, -a) + jnp.eye(n, dtype=a.dtype)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` (..., L, L) strictly lower triangular:
    the diagonal blocks of ``_SOLVE_BLOCK`` rows by :func:`_rows_inverse`,
    all at once (15 steps in a row for a tile of 64, where the rows one
    by one are 63 steps of a few microseconds each, a call and layer),
    then the blocks below the diagonal a block row at a time, ``X[i, :i]
    = -X[i, i] a[i, :i] X[:i, :i]``. A product of powers of ``a`` would be
    the same in exact arithmetic and cancels badly where the keys of
    neighbouring tokens are alike."""
    el, b = a.shape[-1], _SOLVE_BLOCK
    if el <= b or el % b:
        return _rows_inverse(a)
    nb = el // b
    blocks = a.reshape(a.shape[:-2] + (nb, b, nb, b))
    idx = jnp.arange(nb)
    diag = _rows_inverse(jnp.moveaxis(blocks, -2, -3)[..., idx, idx, :, :])
    mm = functools.partial(jnp.matmul, precision=_HI)
    rows = [jnp.pad(diag[..., 0, :, :], [(0, 0)] * (a.ndim - 1)
                    + [(0, el - b)])]
    for i in range(1, nb):
        done = jnp.concatenate(rows, axis=-2)[..., :i * b]   # X[:i, :i]
        below = -mm(diag[..., i, :, :],
                    mm(a[..., i * b:(i + 1) * b, :i * b], done))
        rows.append(jnp.concatenate(
            [below, diag[..., i, :, :],
             jnp.zeros(a.shape[:-2] + (b, el - (i + 1) * b), a.dtype)], -1))
    return jnp.concatenate(rows, axis=-2)


def _scan_factors(q, k, v, g, beta):
    """What the WY form needs of a chunk, tile by tile, per value head:
    ``u`` (S, Hv, T, L, dv), ``w``, ``qg`` (S, Hv, T, L, dk), ``kdt`` (S,
    Hv, T, dk, L), ``qk`` (S, Hv, T, L, L), ``alast`` (S, Hv, T, 1, dv)."""
    s, c, hk, dk = q.shape
    hv, dv = v.shape[2:]
    r = hv // hk
    el = _tile(c)
    t = c // el
    f32 = jnp.float32
    # (S, Hv, T, L, .): a value head reads its key head's q and k
    qh = jnp.repeat(q.astype(f32).reshape(s, t, el, hk, dk), r, axis=3
                    ).transpose(0, 3, 1, 2, 4)
    kh = jnp.repeat(k.astype(f32).reshape(s, t, el, hk, dk), r, axis=3
                    ).transpose(0, 3, 1, 2, 4)
    vh = v.astype(f32).reshape(s, t, el, hv, dv).transpose(0, 3, 1, 2, 4)
    gh = g.astype(f32).reshape(s, t, el, hv).transpose(0, 3, 1, 2)
    bh = beta.astype(f32).reshape(s, t, el, hv).transpose(0, 3, 1, 2)
    cum = jnp.cumsum(gh, axis=-1)                           # (S,Hv,T,L)
    last = cum[..., -1:]
    diff = cum[..., :, None] - cum[..., None, :]            # c_t - c_s
    tri = jnp.tril(jnp.ones((el, el), bool))
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    kb = kh * bh[..., None]
    kk = jnp.einsum("...td,...sd->...ts", kb, kh, precision=_HI)
    strict = jnp.tril(jnp.ones((el, el), bool), -1)
    inv = _unit_lower_inverse(jnp.where(strict, kk * decay, 0.0))
    grown = jnp.exp(cum)[..., None]             # decay from the tile's start
    u = jnp.einsum("...ts,...sd->...td", inv, vh * bh[..., None],
                   precision=_HI)
    w = jnp.einsum("...ts,...sd->...td", inv, kb * grown, precision=_HI)
    qk = jnp.einsum("...td,...sd->...ts", qh, kh, precision=_HI) * decay
    qg = qh * grown
    kdt = jnp.swapaxes(kh * jnp.exp(last - cum)[..., None], -1, -2)
    alast = jnp.broadcast_to(jnp.exp(last)[..., None], (s, hv, t, 1, dv))
    return u, w, qg, kdt, qk, alast


def _scan_kernel(rows_ref, fresh_ref, u_ref, w_ref, qg_ref, kdt_ref, qk_ref,
                 alast_ref, pool_ref, y_ref, out_ref, st_ref):
    del rows_ref                                            # index maps' own
    lane, t = pl.program_id(0), pl.program_id(2)

    @pl.when(t == 0)
    def _start():
        st_ref[...] = jnp.where(fresh_ref[lane] > 0, 0.0,
                                pool_ref[0, 0].astype(jnp.float32))

    st = st_ref[...]                                        # (dk, dv)
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)
    d = u_ref[0, 0, 0] - dot(w_ref[0, 0, 0], st)            # (L, dv)
    y_ref[0] = dot(qg_ref[0, 0, 0], st) + dot(qk_ref[0, 0, 0], d)
    st = alast_ref[0, 0, 0] * st + dot(kdt_ref[0, 0, 0], d)
    st_ref[...] = st

    @pl.when(t == pl.num_programs(2) - 1)
    def _store():
        out_ref[0, 0] = st.astype(out_ref.dtype)


def _scan_pallas(q, k, v, g, beta, pool, rows, fresh, *, block_sizes=None,
                 interpret=False):
    del block_sizes
    s, c, hk, dk = q.shape
    hv, dv = v.shape[2:]
    el = _tile(c)
    nt = c // el
    u, w, qg, kdt, qk, alast = _scan_factors(q, k, v, g, beta)

    def tile(*last):
        return pl.BlockSpec((1, 1, 1) + last,
                            lambda i, j, t, *_: (i, j, t, 0, 0))

    def state(i, j, t, rows_ref, _fresh):
        return (rows_ref[i], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(s, hv, nt),
        in_specs=[tile(el, dv), tile(el, dk), tile(el, dk), tile(dk, el),
                  tile(el, el), tile(1, dv),
                  pl.BlockSpec((1, 1, dk, dv), state)],
        out_specs=[pl.BlockSpec((1, el, dv), lambda i, j, t, *_: (i, t, j)),
                   pl.BlockSpec((1, 1, dk, dv), state)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)])
    y, pool = pl.pallas_call(
        _scan_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, c, hv * dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret, name="gated_delta_chunk_scan",
    )(rows.astype(jnp.int32), fresh.astype(jnp.int32), u, w, qg, kdt, qk,
      alast, pool)
    return y.reshape(s, c, hv, dv), pool


def _scan_lax(q, k, v, g, beta, pool, rows, fresh):
    """The same tiles and the same products through XLA."""
    s, c = q.shape[:2]
    hv, dv = v.shape[2:]
    u, w, qg, kdt, qk, alast = _scan_factors(q, k, v, g, beta)
    st = jnp.where((fresh > 0)[:, None, None, None], 0.0,
                   pool[rows].astype(jnp.float32))          # (S,Hv,dk,dv)
    mm = functools.partial(jnp.einsum, precision=_HI)
    ys = []
    for t in range(u.shape[2]):
        d = u[:, :, t] - mm("bhtk,bhkv->bhtv", w[:, :, t], st)
        ys.append(mm("bhtk,bhkv->bhtv", qg[:, :, t], st)
                  + mm("bhts,bhsv->bhtv", qk[:, :, t], d))
        st = alast[:, :, t] * st + mm("bhkt,bhtv->bhkv", kdt[:, :, t], d)
    y = jnp.concatenate(ys, axis=2).transpose(0, 2, 1, 3)   # (S,C,Hv,dv)
    return y.reshape(s, c, hv, dv), pool.at[rows].set(st.astype(pool.dtype))


def _recurrence(q, k, v, decay, beta, st):
    """Token by token in float64 numpy: ``q``, ``k`` (C, Hk, dk), ``v``
    (C, Hv, dv), ``decay`` = exp(g) and ``beta`` (C, Hv), ``st`` (Hv, dk,
    dv) -> (o (C, Hv, dv), state)."""
    import numpy as np
    c, hk, _ = q.shape
    hv = v.shape[1]
    r = hv // hk
    st = np.array(st, np.float64)
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    o = np.zeros(v.shape)
    for t in range(c):
        for i in range(hv):
            s_i = float(decay[t, i]) * st[i]
            d = float(beta[t, i]) * (v[t, i] - k[t, i // r] @ s_i)
            st[i] = s_i + np.outer(k[t, i // r], d)
            o[t, i] = q[t, i // r] @ st[i]
    return o, st


def _scan_reference(q, k, v, g, beta, pool, rows, fresh):
    import numpy as np
    out = np.array(pool, np.float64)
    ys = []
    for lane in range(q.shape[0]):
        r = int(rows[lane])
        start = np.zeros_like(out[r]) if int(fresh[lane]) else out[r]
        y, out[r] = _recurrence(q[lane], k[lane], v[lane],
                                np.exp(np.asarray(g[lane], np.float64)),
                                np.asarray(beta[lane]), start)
        ys.append(y)
    return (jnp.asarray(np.stack(ys), jnp.float32),
            jnp.asarray(out, jnp.float32))


def _sample(seed, chunk):
    """(q, k, v, g, beta, pool, rows, fresh) at a test's size; lane 1
    starts fresh, the last lane is a pad lane on the null row, and the
    tail of every lane is pad (``g`` and ``beta`` 0). Keys of neighbouring
    tokens share a direction, as a conv's outputs do."""
    import numpy as np
    s, hk, hv, dk, dv = 3, 2, 4, 16, 16
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa

    def unit(a):
        return a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    k = unit(f(s, chunk, hk, dk) + 0.7 * f(s, 1, hk, dk))
    q = unit(f(s, chunk, hk, dk)) * dk ** -0.5
    g = -np.exp(f(hv)) * np.log1p(np.exp(f(s, chunk, hv)))
    beta = 1.0 / (1.0 + np.exp(-f(s, chunk, hv)))
    if chunk > 1:
        g[:, chunk - 1 - seed % 3:] = 0.0
        beta[:, chunk - 1 - seed % 3:] = 0.0
    g[-1] = 0.0
    beta[-1] = 0.0
    return (q, k, f(s, chunk, hv, dv), g, beta, f(s + 2, hv, dk, dv),
            np.array([2, 4, 0], np.int32), np.array([0, 1, 0], np.int32))


def _make_scan_sample(seed):
    return tuple(jnp.asarray(a) for a in _sample(seed, (8, 6, 1)[seed % 3])
                 ), {}


def gated_delta_chunk_scan(q, k, v, g, beta, pool, rows, fresh, *,
                           impl: str = "auto"):
    """A chunk of ``C`` tokens a lane through the gated delta rule, from
    the state in the lane's pool row (zero where ``fresh``), the row left
    holding the state after the chunk.

    ``q`` / ``k`` (S, C, Hk, dk), ``v`` (S, C, Hv, dv), ``g`` (the log
    decay) and ``beta`` (S, C, Hv) float32, both 0 at a pad position,
    ``pool`` (R, Hv, dk, dv), ``rows`` / ``fresh`` (S,) int32. Returns (o
    (S, C, Hv, dv) float32, pool)."""
    from paddle_tpu import kernels
    return kernels.dispatch("gated_delta_chunk_scan", q, k, v, g, beta, pool,
                            rows, fresh, impl=impl)


# ---------------------------------------------------------------------------
# one token a slot
# ---------------------------------------------------------------------------

def _decode_kernel(rows_ref, q_ref, k_ref, v_ref, decay_ref, beta_ref,
                   pool_ref, o_ref, out_ref):
    live = rows_ref[pl.program_id(0)] > 0
    hb, dk, dv = pool_ref.shape[1:]
    r = hb // q_ref.shape[1]

    @pl.when(live)
    def _update():
        for jk in range(hb // r):
            # the key head's k and q, each entry along the lanes of its
            # row of the tile: (dk, dv)
            k_wide = jnp.broadcast_to(k_ref[0, jk:jk + 1, :], (dv, dk)).T
            q_wide = jnp.broadcast_to(q_ref[0, jk:jk + 1, :], (dv, dk)).T
            for j in range(jk * r, (jk + 1) * r):
                st = decay_ref[0, j:j + 1, :] \
                    * pool_ref[0, j].astype(jnp.float32)
                d = beta_ref[0, j:j + 1, :] * (
                    v_ref[0, j:j + 1, :]
                    - jnp.sum(st * k_wide, axis=0, keepdims=True))
                st = st + k_wide * d
                out_ref[0, j] = st.astype(out_ref.dtype)
                o_ref[0, j:j + 1, :] = jnp.sum(st * q_wide, axis=0,
                                               keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _skip():
        out_ref[...] = pool_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _head_block(hv: int, r: int, dk: int, dv: int) -> int:
    """Value heads a decode grid step: the most, in whole key heads, whose
    tiles stay under the block budget."""
    hb = hv
    while hb > r and (hb * dk * dv * 4 > _DECODE_BLOCK_BYTES or hv % hb
                      or hb % r):
        hb -= 1
    return hb


def _decode_pallas(q, k, v, decay, beta, pool, rows, *, block_sizes=None,
                   interpret=False):
    del block_sizes
    s, hk, dk = q.shape
    hv, dv = v.shape[1:]
    r = hv // hk
    hb = _head_block(hv, r, dk, dv)
    wide = lambda a: jnp.broadcast_to(                       # noqa: E731
        a.astype(jnp.float32)[:, :, None], (s, hv, dv))

    def heads(i, j, _rows):
        return (i, j, 0)

    def state(i, j, rows_ref):
        return (rows_ref[i], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(s, hv // hb),
        in_specs=[pl.BlockSpec((1, hb // r, dk), heads),
                  pl.BlockSpec((1, hb // r, dk), heads),
                  pl.BlockSpec((1, hb, dv), heads),
                  pl.BlockSpec((1, hb, dv), heads),
                  pl.BlockSpec((1, hb, dv), heads),
                  pl.BlockSpec((1, hb, dk, dv), state)],
        out_specs=[pl.BlockSpec((1, hb, dv), heads),
                   pl.BlockSpec((1, hb, dk, dv), state)])
    o, pool = pl.pallas_call(
        _decode_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, hv, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret, name="gated_delta_decode_update",
    )(rows.astype(jnp.int32), q.astype(jnp.float32), k.astype(jnp.float32),
      v.astype(jnp.float32), wide(decay), wide(beta), pool)
    return o, pool


def _decode_lax(q, k, v, decay, beta, pool, rows):
    hv = v.shape[1]
    r = hv // q.shape[1]
    live = (rows > 0)[:, None, None, None]
    qh = jnp.repeat(q.astype(jnp.float32), r, axis=1)       # (S,Hv,dk)
    kh = jnp.repeat(k.astype(jnp.float32), r, axis=1)
    old = pool[rows]
    st = decay[:, :, None, None] * old.astype(jnp.float32)
    d = beta[:, :, None] * (v - jnp.sum(st * kh[..., None], axis=2))
    st = st + kh[..., None] * d[:, :, None, :]
    o = jnp.where(live[..., 0], jnp.sum(st * qh[..., None], axis=2), 0.0)
    st = jnp.where(live, st.astype(pool.dtype), old)
    return o, pool.at[rows].set(st)


def _decode_reference(q, k, v, decay, beta, pool, rows):
    import numpy as np
    out = np.array(pool, np.float64)
    os_ = np.zeros(v.shape)
    for lane in range(q.shape[0]):
        r = int(rows[lane])
        if r:
            o, out[r] = _recurrence(
                np.asarray(q[lane])[None], np.asarray(k[lane])[None],
                np.asarray(v[lane])[None], np.asarray(decay[lane])[None],
                np.asarray(beta[lane])[None], out[r])
            os_[lane] = o[0]
    return jnp.asarray(os_, jnp.float32), jnp.asarray(out, jnp.float32)


def _make_decode_sample(seed):
    import numpy as np
    q, k, v, g, beta, pool, rows, _fresh = _sample(seed + 7, 1)
    g[-1], beta[-1] = -0.3, 0.5     # the dead slot's own: never applied
    return tuple(jnp.asarray(a) for a in (
        q[:, 0], k[:, 0], v[:, 0], np.exp(g[:, 0]), beta[:, 0], pool,
        rows)), {}


def gated_delta_decode_update(q, k, v, decay, beta, pool, rows, *,
                              impl: str = "auto"):
    """One token a slot: ``q`` / ``k`` (S, Hk, dk), ``v`` (S, Hv, dv),
    ``decay`` = exp(g) and ``beta`` (S, Hv), the state of slot ``s`` in
    pool row ``rows[s]``; a slot whose row is 0 is dead: its ``o`` is 0
    and no row changes. Returns (o (S, Hv, dv) float32, pool)."""
    from paddle_tpu import kernels
    return kernels.dispatch("gated_delta_decode_update", q, k, v, decay,
                            beta, pool, rows, impl=impl)


def _decode_vmem_estimate(args, kwargs, blocks):
    """What a decode grid step holds in VMEM: its state tiles in and out,
    each double-buffered, and a key head's two wide operands."""
    del kwargs, blocks
    hk, dk = args[0].shape[1:]
    hv, dv = args[2].shape[1:]
    hb = _head_block(hv, hv // hk, dk, dv)
    return 4 * hb * dk * dv * 4 + 2 * dk * dv * 4


def _scan_vmem_estimate(args, kwargs, blocks):
    """A scan grid step: the tile's six factors and its output, each
    double-buffered, the state tile in, out and carried."""
    del kwargs, blocks
    c, _, dk = args[0].shape[1:]
    dv = args[2].shape[-1]
    el = _tile(c)
    factors = el * (2 * dv + 2 * dk + dk + el) + dv
    return 4 * (2 * factors + 5 * dk * dv)


def _register():
    from paddle_tpu import kernels
    from paddle_tpu.ops.ssm_scan import _donation_probe, _parity
    layouts = {"q": "(S,C,Hk,dk)", "k": "(S,C,Hk,dk)", "v": "(S,C,Hv,dv)",
               "g": "(S,C,Hv)", "beta": "(S,C,Hv)", "pool": "(R,Hv,dk,dv)",
               "rows": "(S,) i32", "fresh": "(S,) i32"}
    kernels.register(kernels.KernelSpec(
        name="gated_delta_chunk_scan",
        contract=kernels.KernelContract(
            version=1, arg_layouts=layouts,
            out_layout="(S,C,Hv,dv), (R,Hv,dk,dv)", donatable=("pool",),
            grid="(lanes, value heads, C/L): a lane's state tile from its "
                 "scalar-prefetched pool row, carried over the tiles of "
                 "its chunk and written back in place",
            atol=2e-4, rtol=2e-4),
        pallas_fn=_scan_pallas, lax_fn=_scan_lax,
        reference_fn=_scan_reference, sample_inputs=_make_scan_sample,
        parity_fn=_parity("gated_delta_chunk_scan"),
        donation_probe=_donation_probe(_scan_pallas, _make_scan_sample),
        vmem_estimate=_scan_vmem_estimate,
        pallas_sites=("paddle_tpu.ops.gated_delta:_scan_pallas",)))
    one = {"decay" if k == "g" else k: v.replace("S,C,", "S,")
           for k, v in layouts.items() if k != "fresh"}
    kernels.register(kernels.KernelSpec(
        name="gated_delta_decode_update",
        contract=kernels.KernelContract(
            version=1, arg_layouts=one,
            out_layout="(S,Hv,dv), (R,Hv,dk,dv)", donatable=("pool",),
            grid="(slots, head blocks): a live slot's (dk, dv) tiles read "
                 "once and written once in place, a dead slot skipped",
            atol=2e-5, rtol=2e-5),
        pallas_fn=_decode_pallas, lax_fn=_decode_lax,
        reference_fn=_decode_reference, sample_inputs=_make_decode_sample,
        parity_fn=_parity("gated_delta_decode_update"),
        donation_probe=_donation_probe(_decode_pallas, _make_decode_sample),
        vmem_estimate=_decode_vmem_estimate,
        pallas_sites=("paddle_tpu.ops.gated_delta:_decode_pallas",)))


_register()

"""Selective state-space recurrence (the Mamba-2 form) over a pool of
per-slot states: a chunked scan for a prompt chunk, a one-token update
for a decode step.

Per head ``h`` of ``P`` channels, with ``N`` state dims shared by the
heads of a group ``g``, token ``t``::

    S_t = exp(dt_t * a_h) S_{t-1} + (dt_t * x_t) (x) B_t      S in R^{P x N}
    y_t = S_t C_t

(``a_h`` negative; the ``D_h * x_t`` skip, the gate and the norm belong to
the model). A token whose ``dt`` is 0 changes nothing and adds nothing:
that is how a chunk's pad positions are left out.

The states live in a pool ``(R, H, N, P)`` float32, one row a serving
slot, row 0 the null row pad lanes and dead slots point at. The tile of a
head is kept state-major, ``(N, P)``: a token's ``x`` and ``y`` are then
rows along the tile's lanes, and the update is ``S^T = a S^T + B x^T``
with no transpose of a per-head operand. Both kernels take the pool, the
pool row of every lane, and hand the pool back updated in place
(``input_output_aliases``): a tile is read once and written once.

``ssd_chunk_scan``: grid ``(lanes, heads, C / L)`` over tiles of ``L =
min(C, 128)`` tokens, the matmul form of the recurrence inside a tile::

    y    = ((C B^T) * decay[t, s]) (dt x)  +  exp(cum_t) C S_start^T
    S^T  = exp(cum_L) S_start^T  +  B^T (dt x * exp(cum_L - cum_s))

with ``cum`` the running sum of ``dt * a`` inside the tile; the decay
factors are prepared by XLA (elementwise over arrays of ``x``'s size), the
four products and the carried state are the kernel's. ``fresh`` lanes
start from a zero state whatever their row holds (a slot's reset at
admission).

``ssm_decode_update``: grid ``(slots, head blocks)``, one token a slot,
on the VPU: ``S^T = a S^T + B (dt x)^T`` and ``y = C^T S^T`` as a sublane
sum. A slot whose row is 0 is skipped: nothing of its tile is computed
and the null row is written back as it was read.

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
SCAN_TILE = 128
#: bytes of state tiles one decode grid step holds, in and out each
_DECODE_BLOCK_BYTES = 2 << 20


def _tile(c: int) -> int:
    if c <= SCAN_TILE:
        return c
    if c % SCAN_TILE:
        raise ValueError(f"a chunk of {c} tokens is no multiple of the "
                         f"scan tile of {SCAN_TILE}")
    return SCAN_TILE


def _scan_factors(x, dt, a, bm, cm, n_groups):
    """What the matmul form needs of the decay, tile by tile:
    ``dtx, xw, ecum`` (S, C, H*P), ``lmat`` (S, H, T, L, L), ``alast``
    (S, T, H, 1, P), ``bt`` (S, G, N, C)."""
    s, c, hp = x.shape
    h = dt.shape[-1]
    p = hp // h
    n = bm.shape[-1] // n_groups
    el = _tile(c)
    t = c // el
    la = (dt * a).reshape(s, t, el, h)                      # log decay <= 0
    cum = jnp.cumsum(la, axis=2)                            # inclusive
    last = cum[:, :, -1:, :]                                # (S,T,1,H)

    def wide(v):                                            # (S,T,L,H) -> x's
        return jnp.repeat(v.reshape(s, c, h), p, axis=-1)

    dtx = x * jnp.repeat(dt, p, axis=-1)
    xw = dtx * wide(jnp.exp(last - cum))
    ecum = wide(jnp.exp(cum))
    ct = cum.transpose(0, 3, 1, 2)                          # (S,H,T,L)
    diff = ct[..., :, None] - ct[..., None, :]              # cum_t - cum_s
    causal = jnp.tril(jnp.ones((el, el), bool))
    lmat = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    alast = jnp.broadcast_to(
        jnp.exp(last).reshape(s, t, h, 1, 1), (s, t, h, 1, p))
    bt = bm.reshape(s, c, n_groups, n).transpose(0, 2, 3, 1)
    return dtx, xw, ecum, lmat, alast, bt


def _scan_kernel(rows_ref, fresh_ref, cm_ref, bt_ref, dtx_ref, xw_ref,
                 ecum_ref, lmat_ref, alast_ref, pool_ref, y_ref, out_ref,
                 st_ref):
    del rows_ref                                            # index maps' own
    lane, t = pl.program_id(0), pl.program_id(2)

    @pl.when(t == 0)
    def _start():
        st_ref[...] = jnp.where(fresh_ref[lane] > 0, 0.0,
                                pool_ref[0, 0].astype(jnp.float32))

    st = st_ref[...]                                        # (N, P)
    c, bt = cm_ref[0], bt_ref[0, 0]                         # (L,N), (N,L)
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)
    within = dot(dot(c, bt) * lmat_ref[0, 0, 0], dtx_ref[0])
    y_ref[0] = within + ecum_ref[0] * dot(c, st)
    st = alast_ref[0, 0, 0] * st + dot(bt, xw_ref[0])
    st_ref[...] = st

    @pl.when(t == pl.num_programs(2) - 1)
    def _store():
        out_ref[0, 0] = st.astype(out_ref.dtype)


def _scan_pallas(x, dt, a, bm, cm, pool, rows, fresh, *, n_groups,
                 block_sizes=None, interpret=False):
    del block_sizes
    s, c, hp = x.shape
    h = dt.shape[-1]
    p = hp // h
    n = bm.shape[-1] // n_groups
    hpg = h // n_groups
    el = _tile(c)
    nt = c // el
    dtx, xw, ecum, lmat, alast, bt = _scan_factors(x, dt, a, bm, cm,
                                                   n_groups)

    def tokens(i, j, t, *_):
        return (i, t, j)

    def state(i, j, t, rows_ref, _fresh):
        return (rows_ref[i], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(s, h, nt),
        in_specs=[
            pl.BlockSpec((1, el, n), lambda i, j, t, *_: (i, t, j // hpg)),
            pl.BlockSpec((1, 1, n, el),
                         lambda i, j, t, *_: (i, j // hpg, 0, t)),
            pl.BlockSpec((1, el, p), tokens),
            pl.BlockSpec((1, el, p), tokens),
            pl.BlockSpec((1, el, p), tokens),
            pl.BlockSpec((1, 1, 1, el, el),
                         lambda i, j, t, *_: (i, j, t, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, p),
                         lambda i, j, t, *_: (i, t, j, 0, 0)),
            pl.BlockSpec((1, 1, n, p), state)],
        out_specs=[pl.BlockSpec((1, el, p), tokens),
                   pl.BlockSpec((1, 1, n, p), state)],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)])
    y, pool = pl.pallas_call(
        _scan_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret, name="ssd_chunk_scan",
    )(rows.astype(jnp.int32), fresh.astype(jnp.int32), cm, bt, dtx, xw,
      ecum, lmat, alast, pool)
    return y, pool


def _scan_lax(x, dt, a, bm, cm, pool, rows, fresh, *, n_groups):
    """The same tiles and the same products through XLA."""
    s, c, hp = x.shape
    h = dt.shape[-1]
    p = hp // h
    n = bm.shape[-1] // n_groups
    hpg = h // n_groups
    el = _tile(c)
    nt = c // el
    dtx, xw, ecum, lmat, alast, bt = _scan_factors(x, dt, a, bm, cm,
                                                   n_groups)
    st = jnp.where((fresh > 0)[:, None, None, None], 0.0,
                   pool[rows].astype(jnp.float32))          # (S,H,N,P)
    heads = lambda v: v.reshape(s, nt, el, h, p)            # noqa: E731
    dtx, xw, ecum = heads(dtx), heads(xw), heads(ecum)
    cg = cm.reshape(s, nt, el, n_groups, n)
    btg = bt.reshape(s, n_groups, n, nt, el)
    ys = []
    for t in range(nt):
        ch = jnp.repeat(cg[:, t], hpg, axis=2)              # (S,L,H,N)
        bh = jnp.repeat(btg[:, :, :, t], hpg, axis=1)       # (S,H,N,L)
        g = jnp.einsum("slhn,shnm->shlm", ch, bh, precision=_HI)
        within = jnp.einsum("shlm,smhp->slhp", g * lmat[:, :, t],
                            dtx[:, t], precision=_HI)
        carried = jnp.einsum("slhn,shnp->slhp", ch, st, precision=_HI)
        ys.append(within + ecum[:, t] * carried)
        st = alast[:, t] * st + jnp.einsum(
            "shnl,slhp->shnp", bh, xw[:, t], precision=_HI)
    y = jnp.concatenate(ys, axis=1).reshape(s, c, hp)
    return y, pool.at[rows].set(st.astype(pool.dtype))


def _recurrence(x, dt, a, bm, cm, st, n_groups):
    """Token by token in float64 numpy: ``x`` (C, H*P), ``st`` (H, N, P)
    -> (y (C, H*P), state)."""
    import numpy as np
    c, hp = x.shape
    h = dt.shape[-1]
    p, n = hp // h, bm.shape[-1] // n_groups
    hpg = h // n_groups
    st = np.array(st, np.float64)
    y = np.zeros((c, h, p))
    xs = np.asarray(x, np.float64).reshape(c, h, p)
    bs = np.asarray(bm, np.float64).reshape(c, n_groups, n)
    cs = np.asarray(cm, np.float64).reshape(c, n_groups, n)
    for t in range(c):
        for j in range(h):
            d = float(dt[t, j])
            st[j] = np.exp(d * float(a[j])) * st[j] + np.outer(
                bs[t, j // hpg], d * xs[t, j])
            y[t, j] = cs[t, j // hpg] @ st[j]
    return y.reshape(c, hp), st


def _scan_reference(x, dt, a, bm, cm, pool, rows, fresh, *, n_groups):
    import numpy as np
    out = np.array(pool, np.float64)
    ys = []
    for lane in range(x.shape[0]):
        r = int(rows[lane])
        start = np.zeros_like(out[r]) if int(fresh[lane]) else out[r]
        y, out[r] = _recurrence(np.asarray(x[lane]), np.asarray(dt[lane]),
                                np.asarray(a), bm[lane], cm[lane], start,
                                n_groups)
        ys.append(y)
    return (jnp.asarray(np.stack(ys), jnp.float32),
            jnp.asarray(out, jnp.float32))


def _sample(seed, chunk):
    """(x, dt, a, bm, cm, pool, rows, fresh) at a test's size; lane 1
    starts fresh, the last lane is a pad lane on the null row, and the
    tail of every lane is pad (``dt`` 0)."""
    import numpy as np
    s, h, p, g, n = 3, 4, 16, 2, 16
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa
    dt = np.log1p(np.exp(f(s, chunk, h)))
    if chunk > 1:
        dt[:, chunk - 1 - seed % 3:] = 0.0
    dt[-1] = 0.0
    return (f(s, chunk, h * p), dt, -np.exp(f(h) * 0.5),
            f(s, chunk, g * n), f(s, chunk, g * n), f(s + 2, h, n, p),
            np.array([2, 4, 0], np.int32), np.array([0, 1, 0], np.int32)), g


def _make_scan_sample(seed):
    args, g = _sample(seed, (8, 6, 1)[seed % 3])
    return tuple(jnp.asarray(v) for v in args), {"n_groups": g}


def ssd_chunk_scan(x, dt, a, bm, cm, pool, rows, fresh, *, n_groups: int,
                   impl: str = "auto"):
    """A chunk of ``C`` tokens a lane through the recurrence, from the
    state in the lane's pool row (zero where ``fresh``), the row left
    holding the state after the chunk.

    ``x`` (S, C, H*P), ``dt`` (S, C, H) float32 (0 at a pad position),
    ``a`` (H,), ``bm`` / ``cm`` (S, C, G*N), ``pool`` (R, H, N, P),
    ``rows`` / ``fresh`` (S,) int32. Returns (y (S, C, H*P) float32,
    pool)."""
    from paddle_tpu import kernels
    return kernels.dispatch("ssd_chunk_scan", x, dt, a, bm, cm, pool, rows,
                            fresh, impl=impl, n_groups=n_groups)


# ---------------------------------------------------------------------------
# one token a slot
# ---------------------------------------------------------------------------

def _decode_kernel(rows_ref, dtx_ref, decay_ref, bm_ref, cm_ref, pool_ref,
                   y_ref, out_ref):
    live = rows_ref[pl.program_id(0)] > 0
    hb, n, p = pool_ref.shape[1:]

    @pl.when(live)
    def _update():
        # B and C of the block's group, each state dim's value along the
        # lanes of its row: (N, P)
        b_wide = jnp.broadcast_to(bm_ref[0, 0], (p, n)).T
        c_wide = jnp.broadcast_to(cm_ref[0, 0], (p, n)).T
        for j in range(hb):
            st = decay_ref[0, j:j + 1, :] * pool_ref[0, j].astype(jnp.float32) \
                + b_wide * dtx_ref[0, j:j + 1, :]
            out_ref[0, j] = st.astype(out_ref.dtype)
            y_ref[0, j:j + 1, :] = jnp.sum(st * c_wide, axis=0,
                                           keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _skip():
        out_ref[...] = pool_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def _head_block(h: int, hpg: int, n: int, p: int) -> int:
    """Heads a decode grid step: the most of one group whose tiles stay
    under the block budget."""
    hb = hpg
    while hb > 1 and (hb * n * p * 4 > _DECODE_BLOCK_BYTES or hpg % hb):
        hb -= 1
    return hb


def _decode_pallas(x, dt, a, bm, cm, pool, rows, *, n_groups,
                   block_sizes=None, interpret=False):
    del block_sizes
    s, hp = x.shape
    h = dt.shape[-1]
    p = hp // h
    n = bm.shape[-1] // n_groups
    hpg = h // n_groups
    hb = _head_block(h, hpg, n, p)
    dtx = (x.reshape(s, h, p) * dt[:, :, None])
    decay = jnp.broadcast_to(jnp.exp(dt * a)[:, :, None], (s, h, p))

    def heads(i, j, _rows):
        return (i, j, 0)

    def group(i, j, _rows):
        return (i, (j * hb) // hpg, 0, 0)

    def state(i, j, rows_ref):
        return (rows_ref[i], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(s, h // hb),
        in_specs=[pl.BlockSpec((1, hb, p), heads),
                  pl.BlockSpec((1, hb, p), heads),
                  pl.BlockSpec((1, 1, 1, n), group),
                  pl.BlockSpec((1, 1, 1, n), group),
                  pl.BlockSpec((1, hb, n, p), state)],
        out_specs=[pl.BlockSpec((1, hb, p), heads),
                   pl.BlockSpec((1, hb, n, p), state)])
    y, pool = pl.pallas_call(
        _decode_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, h, p), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret, name="ssm_decode_update",
    )(rows.astype(jnp.int32), dtx, decay,
      bm.reshape(s, n_groups, 1, n), cm.reshape(s, n_groups, 1, n), pool)
    return y.reshape(s, hp), pool


def _decode_lax(x, dt, a, bm, cm, pool, rows, *, n_groups):
    s, hp = x.shape
    h = dt.shape[-1]
    p = hp // h
    n = bm.shape[-1] // n_groups
    hpg = h // n_groups
    live = (rows > 0)[:, None, None, None]
    bh = jnp.repeat(bm.reshape(s, n_groups, n), hpg, axis=1)    # (S,H,N)
    ch = jnp.repeat(cm.reshape(s, n_groups, n), hpg, axis=1)
    dtx = x.reshape(s, h, p) * dt[:, :, None]
    old = pool[rows]
    st = jnp.exp(dt * a)[:, :, None, None] * old.astype(jnp.float32) \
        + bh[:, :, :, None] * dtx[:, :, None, :]
    y = jnp.where(live[..., 0], jnp.sum(st * ch[:, :, :, None], axis=2), 0.0)
    st = jnp.where(live, st.astype(pool.dtype), old)
    return y.reshape(s, hp), pool.at[rows].set(st)


def _decode_reference(x, dt, a, bm, cm, pool, rows, *, n_groups):
    import numpy as np
    out = np.array(pool, np.float64)
    ys = np.zeros(x.shape)
    for lane in range(x.shape[0]):
        r = int(rows[lane])
        if r:
            ys[lane], out[r] = _recurrence(
                np.asarray(x[lane])[None], np.asarray(dt[lane])[None],
                np.asarray(a), np.asarray(bm[lane])[None],
                np.asarray(cm[lane])[None], out[r], n_groups)
    return jnp.asarray(ys, jnp.float32), jnp.asarray(out, jnp.float32)


def _make_decode_sample(seed):
    (x, dt, a, bm, cm, pool, rows, _fresh), g = _sample(seed + 7, 1)
    dt[-1] = 0.3                    # the dead slot's own dt: never applied
    return tuple(jnp.asarray(v) for v in (
        x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], pool, rows)), \
        {"n_groups": g}


def ssm_decode_update(x, dt, a, bm, cm, pool, rows, *, n_groups: int,
                      impl: str = "auto"):
    """One token a slot: ``x`` (S, H*P), ``dt`` (S, H), ``bm`` / ``cm``
    (S, G*N), the state of slot ``s`` in pool row ``rows[s]``; a slot
    whose row is 0 is dead: its ``y`` is 0 and no row changes. Returns
    (y (S, H*P) float32, pool)."""
    from paddle_tpu import kernels
    return kernels.dispatch("ssm_decode_update", x, dt, a, bm, cm, pool,
                            rows, impl=impl, n_groups=n_groups)


def _parity(name):
    """Both outputs of kernel ``name`` (``y`` and the pool), the ``lax``
    form and the interpreted Pallas body, against the token-by-token
    recurrence; the rows no lane holds bit for bit."""
    def check(seed):
        import numpy as np

        from paddle_tpu import kernels
        spec = kernels.get(name)
        args, kw = spec.sample_inputs(seed)
        want = [np.asarray(v) for v in spec.reference_fn(*args, **kw)]
        pool, rows = np.asarray(args[5]), np.asarray(args[6])
        idle = np.setdiff1d(np.arange(1, pool.shape[0]), rows)
        errs = {}
        for impl in ("lax", "pallas_interpret"):
            got = [np.asarray(v) for v in
                   kernels.dispatch(name, *args, impl=impl, **kw)]
            for g, w in zip(got, want):
                np.testing.assert_allclose(
                    g, w, atol=spec.contract.atol, rtol=spec.contract.rtol,
                    err_msg=f"{name}[{impl}] diverged from the recurrence")
            assert (got[1][idle] == pool[idle]).all(), \
                f"{name}[{impl}] touched a row no lane holds"
            errs[impl] = float(max(np.abs(g - w).max()
                                   for g, w in zip(got, want)))
        return errs
    return check


def _donation_probe(body, make_sample):
    """The pool donated into a step that updates it through the Pallas
    body, as the engine's steps do."""
    def probe():
        args, kw = make_sample(0)

        def step(pool, *rest):
            return body(*rest[:5], pool, *rest[5:], interpret=True, **kw)

        shapes = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                       for a in (args[5],) + args[:5] + args[6:])
        return step, shapes, (0,)
    return probe


def _register():
    from paddle_tpu import kernels
    layouts = {"x": "(S,C,H*P)", "dt": "(S,C,H)", "a": "(H,)",
               "bm": "(S,C,G*N)", "cm": "(S,C,G*N)", "pool": "(R,H,N,P)",
               "rows": "(S,) i32", "fresh": "(S,) i32"}
    kernels.register(kernels.KernelSpec(
        name="ssd_chunk_scan",
        contract=kernels.KernelContract(
            version=1, arg_layouts=layouts,
            out_layout="(S,C,H*P), (R,H,N,P)", donatable=("pool",),
            grid="(lanes, heads, C/L): a lane's state tile from its "
                 "scalar-prefetched pool row, carried over the tiles of "
                 "its chunk and written back in place",
            atol=2e-4, rtol=2e-4),
        pallas_fn=_scan_pallas, lax_fn=_scan_lax,
        reference_fn=_scan_reference, sample_inputs=_make_scan_sample,
        parity_fn=_parity("ssd_chunk_scan"),
        donation_probe=_donation_probe(_scan_pallas, _make_scan_sample),
        pallas_sites=("paddle_tpu.ops.ssm_scan:_scan_pallas",)))
    one = {k: v.replace("S,C,", "S,") for k, v in layouts.items()
           if k != "fresh"}
    kernels.register(kernels.KernelSpec(
        name="ssm_decode_update",
        contract=kernels.KernelContract(
            version=1, arg_layouts=one,
            out_layout="(S,H*P), (R,H,N,P)", donatable=("pool",),
            grid="(slots, head blocks): a live slot's (N, P) tiles read "
                 "once and written once in place, a dead slot skipped",
            atol=2e-5, rtol=2e-5),
        pallas_fn=_decode_pallas, lax_fn=_decode_lax,
        reference_fn=_decode_reference, sample_inputs=_make_decode_sample,
        parity_fn=_parity("ssm_decode_update"),
        donation_probe=_donation_probe(_decode_pallas, _make_decode_sample),
        pallas_sites=("paddle_tpu.ops.ssm_scan:_decode_pallas",)))


_register()

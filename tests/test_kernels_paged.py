"""The paged attention kernels beyond the parity battery of
``tests/test_kernels.py``: the folded page pool (a relayout, never a change
of result), grouped-query heads, the decode body that folds a page for all
its heads, the sparse decode's call and VMEM estimate (the pages
themselves are in ``tests/test_paged_attention.py``). A file of its own
for ``--dist loadfile``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import kernels
from paddle_tpu.kernels import autotune, lint

# the entries whose body folds a block of pages as ONE softmax update:
# bit-equal across ``pages_per_block`` no more, each setting held to the
# reference instead
ONE_UPDATE_A_BLOCK = ("ragged_paged_decode",)

# ---------------------------------------------------------------------------
# the folded page pool (P, ps, H*Dh): a relayout, never a change of result
# ---------------------------------------------------------------------------

PAGED = ("ragged_paged_decode", "ragged_paged_prefill",
         "ragged_paged_decode_int8", "ragged_paged_prefill_int8")


def _lax_on_the_unfolded_pool(name, args):
    """What the lax path computed when the pool was stored (P, ps, H,
    Dh): the 5-D gather contracted head by head, the decode and the
    prefill contraction each as it was, written out here so the folded
    path is held to something that never saw a fold."""
    from paddle_tpu.ops.attention import NEG_INF
    quantized, chunked = name.endswith("int8"), "prefill" in name
    q, kp, vp = args[:3]
    ks, vs = args[3:5] if quantized else (None, None)
    bt, *geo = args[5:] if quantized else args[3:]
    h, dh = q.shape[-2:]
    scale = 1.0 / np.sqrt(dh)
    p, ps = kp.shape[:2]
    kg = kp.reshape(p, ps, h, dh)[bt].astype(jnp.float32)
    vg = vp.reshape(p, ps, h, dh)[bt].astype(jnp.float32)
    s_slots, mp = bt.shape
    tok = jnp.arange(mp * ps, dtype=jnp.int32)
    qf = q.astype(jnp.float32)
    if chunked:
        starts, n_valid = geo
        c = q.shape[1]
        lead = (s_slots, h, c)
        scores = jnp.einsum("schd,smthd->shcmt", qf, kg) * scale
        if quantized:
            scores = scores * ks[bt][:, None, None]
        pos = starts[:, None] + jnp.arange(c, dtype=jnp.int32)
        live = (tok[None, None, None, :] <= pos[:, None, :, None]) & \
            (jnp.arange(c) < n_valid[:, None])[:, None, :, None]
    else:
        lead = (s_slots, h)
        scores = jnp.einsum("shd,smthd->shmt", qf, kg) * scale
        if quantized:
            scores = scores * ks[bt][:, None]
        live = tok[None, None, :] < geo[0][:, None, None]
    scores = jnp.where(live, scores.reshape(lead + (mp * ps,)), NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    alive = jnp.max(scores, axis=-1, keepdims=True) > NEG_INF / 2
    w = jnp.where(alive, w, 0.0).reshape(lead + (mp, ps))
    if quantized:
        w = w * (vs[bt][:, None, None] if chunked else vs[bt][:, None])
    out = jnp.einsum("shcmt,smthd->schd" if chunked
                     else "shmt,smthd->shd", w, vg)
    return out.astype(q.dtype)


class TestFoldedPagePool:
    @pytest.mark.parametrize("name", PAGED)
    def test_samples_and_contracts_declare_the_folded_pool(self, name):
        spec = kernels.get(name)
        args, _ = spec.sample_inputs(2)
        q, kp, vp = args[:3]
        h, dh = q.shape[-2:]
        assert kp.ndim == vp.ndim == 3 and kp.shape[2] == h * dh
        for arg in ("k_pages", "v_pages"):
            layout = spec.contract.arg_layouts[arg]
            assert layout.startswith("(P,ps,H*Dh)"), layout
            assert lint._layout_rank(layout) == 3
        assert lint.contract_findings(spec) == []

    @pytest.mark.parametrize("name", PAGED)
    def test_lax_path_bit_equal_to_the_unfolded_pool(self, name):
        """Folding the heads into the lane axis is a reshape of
        row-major bytes: on the lax path it changes no bit."""
        spec = kernels.get(name)
        for seed in (0, 1, 2):
            args, _ = spec.sample_inputs(seed)
            folded = np.asarray(kernels.dispatch(name, *args, impl="lax"))
            np.testing.assert_array_equal(
                folded, np.asarray(_lax_on_the_unfolded_pool(name, args)))

    @pytest.mark.parametrize("name", PAGED)
    def test_kernel_takes_head_h_from_lanes_h_dh(self, name):
        """Head ``h`` is lanes ``[h*Dh, (h+1)*Dh)`` of a page block and
        nothing else: with the heads of the query and of the folded pool
        reordered alike, the Pallas body (interpreted) gives the same
        heads, reordered, bit for bit — and stays inside the contract's
        tolerance of the lax path fed the same folded pool."""
        spec = kernels.get(name)
        args, _ = spec.sample_inputs(2)
        q, kp, vp = args[:3]
        h, dh = q.shape[-2:]
        perm = np.random.default_rng(0).permutation(h)

        def reorder(pool):
            p, ps, _ = pool.shape
            return pool.reshape(p, ps, h, dh)[:, :, perm].reshape(p, ps, -1)

        out = np.asarray(kernels.dispatch(name, *args,
                                          impl="pallas_interpret"))
        moved = np.asarray(kernels.dispatch(
            name, q[..., perm, :], reorder(kp), reorder(vp), *args[3:],
            impl="pallas_interpret"))
        np.testing.assert_array_equal(moved, out[..., perm, :])
        np.testing.assert_allclose(
            out, np.asarray(kernels.dispatch(name, *args, impl="lax")),
            atol=spec.contract.atol, rtol=spec.contract.rtol)

    @pytest.mark.parametrize("name", PAGED)
    def test_tune_keys_still_hit_the_committed_manifest(self, name):
        """The head count in a tune key comes from ``q`` (the folded
        pool does not carry it); every bucket the offline seeding visits
        (the samples and their per-shard tp twins) must resolve from
        tools/kernel_tune.json — an entry, never a fresh static prior."""
        spec = kernels.get(name)
        tuner = kernels.KernelTuner(kernels.DEFAULT_CACHE_PATH)
        committed = set(tuner.entries)
        samples = [spec.sample_inputs(seed) for seed in (0, 1, 2)]
        samples += [v(seed) for v in spec.tune_sample_variants
                    for seed in (0, 1, 2)]
        for args, kw in filter(None, samples):
            assert kernels.tune_key(spec, args, kw) in committed
            tuner.get(spec, args, kw)
        assert tuner.misses == 0 and tuner.hits > 0

    @pytest.mark.parametrize("name", PAGED)
    def test_vmem_estimate_prices_a_page_block_as_it_is_tiled(self, name):
        """At the serving cell's widths (12 heads of 64, pages of 128) a
        page block is (128, 768): whole tiles in bf16 and in int8, no
        padding of heads."""
        spec = kernels.get(name)
        sds = jax.ShapeDtypeStruct
        quantized, chunked = name.endswith("int8"), "prefill" in name
        q = sds((64, 32, 12, 64) if chunked else (64, 12, 64), jnp.bfloat16)
        pool = sds((513, 128, 768), jnp.int8 if quantized else jnp.bfloat16)
        one = spec.vmem_estimate((q, pool), {}, {"pages_per_block": 1})
        two = spec.vmem_estimate((q, pool), {}, {"pages_per_block": 2})
        page_blocks = 128 * 768 * pool.dtype.itemsize      # unpadded
        scale_rows = 8 * 128 * 4 if quantized else 0
        # one more page a step = a K and a V block (+ scale groups),
        # double-buffered by the pipeline and once more by the estimate;
        # the dense decode body's own two buffers each come to the same,
        # and its one softmax update grows a page wider: 16 rows of
        # float32 scores and weights, the weights' three bf16 terms
        wider_fold = (2 * 16 * 128 * 4 + 48 * 128 * 2
                      if name in ONE_UPDATE_A_BLOCK else 0)
        assert two - one == 2 * 2 * (page_blocks + scale_rows) + wider_fold


# ---------------------------------------------------------------------------
# grouped-query heads in the paged kernels (query head h reads KV head
# h // (H / KV)): the Pallas body against the lax form and a dense NumPy
# reference that repeats nothing
# ---------------------------------------------------------------------------

def _gqa_sample(seed, chunked):
    s, h, kv, dh, ps, mp = ((3, 8, 2, 16, 8, 4), (4, 4, 1, 32, 4, 6))[seed]
    rng = np.random.default_rng(seed)
    num_pages = s * mp + 1
    kp = jnp.asarray(rng.standard_normal((num_pages, ps, kv * dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, ps, kv * dh)),
                     jnp.float32)
    bt = jnp.asarray((rng.permutation(num_pages - 1)[:s * mp] + 1)
                     .reshape(s, mp), jnp.int32)
    if not chunked:
        q = jnp.asarray(rng.standard_normal((s, h, dh)), jnp.float32)
        lengths = jnp.asarray(rng.integers(0, mp * ps + 1, s), jnp.int32)
        return (q, kp, vp, bt, lengths)
    c = ps
    q = jnp.asarray(rng.standard_normal((s, c, h, dh)), jnp.float32)
    starts = jnp.asarray(rng.integers(0, (mp - 1) * ps, s), jnp.int32)
    n_valid = jnp.asarray(rng.integers(0, c + 1, s), jnp.int32)
    return (q, kp, vp, bt, starts, n_valid)


def _gqa_reference(q, kp, vp, bt, *geometry, scale=None):
    q, kp, vp, bt = (np.asarray(a, np.float64) for a in (q, kp, vp, bt))
    bt = bt.astype(int)
    chunked = len(geometry) == 2
    if not chunked:
        q = q[:, None]                                  # (S, 1, H, Dh)
    s, c, h, dh = q.shape
    kv = kp.shape[-1] // dh
    ps = kp.shape[1]
    out = np.zeros_like(q)
    for sl in range(s):
        k = kp[bt[sl]].reshape(-1, kv, dh)
        v = vp[bt[sl]].reshape(-1, kv, dh)
        for r in range(c):
            if chunked:
                if r >= int(geometry[1][sl]):
                    continue
                limit = int(geometry[0][sl]) + r + 1
            else:
                limit = int(geometry[0][sl])
            if limit == 0:
                continue
            for hh in range(h):
                g = hh // (h // kv)
                sc = k[:limit, g] @ q[sl, r, hh] * (scale or dh ** -0.5)
                p = np.exp(sc - sc.max())
                out[sl, r, hh] = (p / p.sum()) @ v[:limit, g]
    del ps
    return out if chunked else out[:, 0]


class TestGroupedQueryHeads:
    @pytest.mark.parametrize("seed", [0, 1])
    @pytest.mark.parametrize("name", ["ragged_paged_decode",
                                      "ragged_paged_prefill"])
    @pytest.mark.parametrize("impl", ["lax", "pallas_interpret"])
    def test_against_dense_reference(self, name, seed, impl):
        args = _gqa_sample(seed, chunked=name.endswith("prefill"))
        want = _gqa_reference(*args)
        got = np.asarray(kernels.dispatch(name, *args, impl=impl))
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("name", ["ragged_paged_decode",
                                      "ragged_paged_prefill"])
    def test_pages_per_block_bit_exact(self, name):
        args = _gqa_sample(0, chunked=name.endswith("prefill"))
        outs = [np.asarray(kernels.dispatch(
            name, *args, impl="pallas_interpret",
            block_sizes={"pages_per_block": pb})) for pb in (1, 2, 4)]
        if name in ONE_UPDATE_A_BLOCK:
            for o in outs:
                np.testing.assert_allclose(o, _gqa_reference(*args),
                                           atol=2e-5, rtol=2e-5)
            return
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)


# ---------------------------------------------------------------------------
# the decode body folds a page ONCE for every head, on operands in the
# dtype they are stored in: bf16 (or int8) pages take one bf16 MXU pass,
# float32 queries and the float32 softmax weights go in as three bf16
# terms, so no operand is rounded. Held to a float64 reference computed
# from the bf16 VALUES, at a tolerance a single-term `P` fails.
# ---------------------------------------------------------------------------

_FOLD_SHAPES = {"mha-12x64": (12, 12, 64), "gqa-32-over-4x128": (32, 4, 128)}
_FOLD_PS, _FOLD_MP = 16, 8
# empty, one token, a page boundary, one past it, the full width, three
# live pages (no multiple of 2, 4 or 8), an empty slot between two live
# ones, six live pages (two blocks of 4, the second half full)
_FOLD_LENGTHS = (0, 1, _FOLD_PS, _FOLD_PS + 1, _FOLD_PS * _FOLD_MP, 37, 0,
                 5 * _FOLD_PS + 3)
_FOLD_PB = (1, 2, 4, 8)


def _fold_sample(shape, pool):
    """float32 queries holding bf16 values (so the output is float32)
    over a bf16, float32 or int8 pool; returns (kernel name, args,
    float64 K, V)."""
    h, kv, dh = _FOLD_SHAPES[shape]
    s = len(_FOLD_LENGTHS)
    rng = np.random.default_rng(h)
    num_pages = s * _FOLD_MP + 1
    q = jnp.asarray(rng.standard_normal((s, h, dh)),
                    jnp.bfloat16).astype(jnp.float32)
    kp, vp = (jnp.asarray(
        rng.standard_normal((num_pages, _FOLD_PS, kv * dh)), jnp.bfloat16)
        for _ in range(2))
    bt = jnp.asarray((rng.permutation(num_pages - 1)[:s * _FOLD_MP] + 1)
                     .reshape(s, _FOLD_MP), jnp.int32)
    lengths = jnp.asarray(_FOLD_LENGTHS, jnp.int32)
    if pool in ("bf16", "f32"):
        k64, v64 = (np.asarray(p.astype(jnp.float32), np.float64)
                    for p in (kp, vp))
        if pool == "f32":       # the same values, multiplied at HIGHEST
            kp, vp = kp.astype(jnp.float32), vp.astype(jnp.float32)
        return "ragged_paged_decode", (q, kp, vp, bt, lengths), k64, v64
    from paddle_tpu.serving.paged_cache import quantize_kv
    kq, ks = quantize_kv(kp.astype(jnp.float32), (2,))
    vq, vs = quantize_kv(vp.astype(jnp.float32), (2,))
    k64, v64 = (np.asarray(p, np.float64)
                * np.asarray(sc, np.float64)[:, :, None]
                for p, sc in ((kq, ks), (vq, vs)))
    return ("ragged_paged_decode_int8", (q, kq, vq, ks, vs, bt, lengths),
            k64, v64)


def _live_pages(bt, lengths, ps):
    """The pool pages that some slot's live extent covers."""
    bt, lengths = np.asarray(bt), np.asarray(lengths)
    return {int(p) for row, n in zip(bt, lengths)
            for p in row[:-(-int(n) // ps)]}


class TestDecodeFoldsAPageForAllHeads:
    TOL = 2e-5

    def _run(self, name, args, pb=2):
        return np.asarray(kernels.dispatch(
            name, *args, impl="pallas_interpret", scale=0.125,
            block_sizes={"pages_per_block": pb}))

    @pytest.mark.parametrize("pb", _FOLD_PB)
    @pytest.mark.parametrize("pool", ["bf16", "f32", "int8"])
    @pytest.mark.parametrize("shape", _FOLD_SHAPES)
    def test_against_float64_on_the_stored_values(self, shape, pool, pb):
        name, args, k64, v64 = _fold_sample(shape, pool)
        want = _gqa_reference(args[0], k64, v64, args[-2], args[-1],
                              scale=0.125)
        got = self._run(name, args, pb)
        assert got.dtype == np.float32
        np.testing.assert_allclose(got, want, atol=self.TOL, rtol=self.TOL)
        # the empty slots: zeros, the first and the one between two live
        assert not got[0].any() and not got[6].any()

    @pytest.mark.parametrize("pool", ["bf16", "int8"])
    @pytest.mark.parametrize("shape", _FOLD_SHAPES)
    def test_pages_per_block_bit_equal(self, shape, pool):
        """The int8 entry keeps the body that folds page by page: bit-
        equal for any setting. The dense entry's body folds a block as
        one update: every setting within the tolerance of float64."""
        name, args, k64, v64 = _fold_sample(shape, pool)
        outs = [self._run(name, args, pb) for pb in (1, 2, 4)]
        if name in ONE_UPDATE_A_BLOCK:
            want = _gqa_reference(args[0], k64, v64, args[-2], args[-1],
                                  scale=0.125)
            for o in outs:
                np.testing.assert_allclose(o, want, atol=self.TOL,
                                           rtol=self.TOL)
            return
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)

    @pytest.mark.parametrize("pool", ["bf16", "f32"])
    @pytest.mark.parametrize("pb", _FOLD_PB)
    def test_only_live_pages_are_read(self, pb, pool):
        """The dense body copies a slot's live pages itself: with every
        pool page that no slot's live extent covers filled with NaN
        (the null page 0 among them) the outputs are the clean pool's."""
        name, args, _k, _v = _fold_sample("gqa-32-over-4x128", pool)
        q, kp, vp, bt, lengths = args
        dead = np.asarray(sorted(
            set(range(kp.shape[0])) - _live_pages(bt, lengths, _FOLD_PS)))
        assert 0 in dead and len(dead) > len(_FOLD_LENGTHS)
        poisoned = tuple(p.at[dead].set(jnp.nan) for p in (kp, vp))
        got = self._run(name, (q, *poisoned, bt, lengths), pb)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, self._run(name, args, pb))

    @pytest.mark.parametrize("pb", _FOLD_PB)
    def test_nothing_stale_is_folded(self, pb):
        """What a buffer held before reaches no output: a full-length
        slot of huge values (whose weights times a small value would
        still be seen), then a one-token slot, then a slot whose last
        block holds one live page, its dead table entries pointing at a
        NaN page."""
        h, kv, dh, ps, mp = 8, 2, 128, _FOLD_PS, 8
        rng = np.random.default_rng(pb)
        lengths = (mp * ps, 1, (pb if pb < mp else pb // 2) * ps + 1)
        num_pages = len(lengths) * mp + 2
        q = jnp.asarray(rng.standard_normal((len(lengths), h, dh)),
                        jnp.bfloat16).astype(jnp.float32)
        kp, vp = (jnp.asarray(
            rng.standard_normal((num_pages, ps, kv * dh)), jnp.bfloat16)
            for _ in range(2))
        bt = np.arange(1, 1 + len(lengths) * mp).reshape(len(lengths), mp)
        huge = jnp.asarray(3e38, jnp.bfloat16)
        kp = kp.at[bt[0]].multiply(huge)
        vp = vp.at[bt[0]].set(huge)
        nan_page = num_pages - 1
        for sl, n in enumerate(lengths):
            bt[sl, -(-n // ps):] = nan_page
        kp, vp = kp.at[nan_page].set(jnp.nan), vp.at[nan_page].set(jnp.nan)
        args = (q, kp, vp, jnp.asarray(bt, jnp.int32),
                jnp.asarray(lengths, jnp.int32))
        k64, v64 = (np.asarray(p.astype(jnp.float32), np.float64)
                    for p in (kp, vp))
        with np.errstate(all="ignore"):     # slot 0's own inf and NaN
            want = _gqa_reference(args[0], k64, v64, args[-2], args[-1],
                                  scale=0.125)
        got = self._run("ragged_paged_decode", args, pb)
        np.testing.assert_allclose(got[1:], want[1:], atol=self.TOL,
                                   rtol=self.TOL)

    @pytest.mark.parametrize("shape", _FOLD_SHAPES)
    def test_one_bf16_term_of_p_is_seen_and_fails(self, shape, monkeypatch):
        """The same body with the split removed (`P`, and a float32
        `q`, rounded to ONE bf16 term: what most flash kernels do) is a
        different result: it misses the tolerance by two orders, where
        the three-term fold sits two orders inside it. The scale is a
        power of two, so the queries stay bf16 values and only `P` is
        rounded."""
        from paddle_tpu.serving import decode_attention as DA
        name, args, k64, v64 = _fold_sample(shape, "bf16")
        want = _gqa_reference(args[0], k64, v64, args[-2], args[-1],
                              scale=0.125)
        split_err = np.abs(self._run(name, args) - want).max()
        monkeypatch.setattr(DA, "_bf16_terms",
                            lambda x: (x.astype(jnp.bfloat16),))
        DA._paged_decode_walk_pallas.clear_cache()   # traced with the split
        try:
            rounded_err = np.abs(self._run(name, args) - want).max()
        finally:
            DA._paged_decode_walk_pallas.clear_cache()
        assert split_err < self.TOL / 20
        assert rounded_err > self.TOL * 50

    def test_only_live_pages_move(self):
        """The pipelined body (the int8 and sparse entries): page
        operand ``t`` of a grid step holds page ``j*pb + t``
        while the slot has it, then its own last live page again (a
        repeated index moves nothing), and pool page 0 where the slot
        never gives it a live page."""
        from paddle_tpu.serving import decode_attention as DA
        ps, pb = 16, 2
        bt = np.arange(100, 108)[None]                       # one slot
        for n_tokens, want in ((0, [[0, 0], [0, 0], [0, 0], [0, 0]]),
                               (1, [[100, 0], [100, 0], [100, 0], [100, 0]]),
                               (ps * 3, [[100, 101], [102, 101],
                                         [102, 101], [102, 101]]),
                               (ps * 8, [[100, 101], [102, 103],
                                         [104, 105], [106, 107]])):
            lens = np.asarray([n_tokens])
            got = [[int(DA._decode_page(bt, lens, 0, j, t, page_size=ps,
                                        pages_per_block=pb))
                    for t in range(pb)] for j in range(4)]
            assert got == want, (n_tokens, got)


# ---------------------------------------------------------------------------
# the chunked-prefill folds take their operands as they are stored too
# (PR 51): bf16 (or int8) pages and bf16 queries one bf16 pass, float32
# queries and the float32 softmax weights three bf16 terms, a float32 pool
# ``HIGHEST``. The decode battery's claims, for the per-head fold, the
# group fold, keys wider than values under a sink and a window, and the
# per-head fold under a selection.
# ---------------------------------------------------------------------------

#: case -> (query heads, KV heads, Dk, Dv, chunk, page, pages a slot,
#: window, sink, selected): ``mha``, ``selected`` and the first ``page128``
#: case run the per-head fold, the other three the group fold (heads x
#: chunk >= 4096), ``wide-keys`` on spans of two 192-lane heads (the
#: long-prompt cell's ``one_span`` path). A chunk of 32 rows multiplies
#: its three terms as one stacked product (96 of the MXU's 128 rows), a
#: chunk of 64 or a group's rows a product a term. The ``page128`` pair,
#: one a fold, has the benchmark cells' page: a row of scores is the 128
#: lanes the fold's state is kept in, so the running maximum meets the
#: scores as it lies (``_row_values``), where a 16-token page takes its
#: first column
_PREFILL_FOLDS = {
    "mha-12x64": (12, 12, 64, 64, 32, 16, 8, None, False, False),
    "gqa-32-over-4x128": (32, 4, 128, 128, 128, 16, 12, None, False, False),
    "wide-keys-sink-window": (32, 2, 192, 128, 128, 16, 12, 70, True, False),
    "selected-8-over-2x64": (8, 2, 64, 64, 64, 16, 8, None, False, True),
    "gqa-8-over-2x128-page128": (8, 2, 128, 128, 32, 128, 3, None, False,
                                 False),
    "gqa-32-over-4x128-page128": (32, 4, 128, 128, 128, 128, 3, None, False,
                                  False),
}
#: the case of the int8 entry (no window, sink, selection or second width
#: there, and grouped-query heads take the same per-head fold)
_PREFILL_INT8 = ("mha-12x64",)
_PREFILL_POOLS = [(case, pool) for case in _PREFILL_FOLDS
                  for pool in ("bf16", "f32", "int8")
                  if pool != "int8" or case in _PREFILL_INT8]


def _prefill_fold_sample(case, pool, q_dtype):
    """Queries holding bf16 values, as float32 (so the output is float32)
    or as bf16, over a bf16, float32 or int8 pool: (kernel name, args,
    keywords, float64 K, V). Slots: a dead one, a full chunk deep in its
    context, a chunk of 5 live rows at the context's start, one that ends
    the table."""
    h, kv, dk, dv, c, ps, mp, window, sink, selected = _PREFILL_FOLDS[case]
    s = 4
    rng = np.random.default_rng(h + dk)
    num_pages = s * mp + 1
    q = jnp.asarray(rng.standard_normal((s, c, h, dk)),
                    jnp.bfloat16).astype(q_dtype)
    kp, vp = (jnp.asarray(
        rng.standard_normal((num_pages, ps, kv * d)), jnp.bfloat16)
        for d in (dk, dv))
    bt = jnp.asarray((rng.permutation(num_pages - 1)[:s * mp] + 1)
                     .reshape(s, mp), jnp.int32)
    starts = jnp.asarray([3, (mp * ps - c) // 2 + 3, 0, mp * ps - c],
                         jnp.int32)
    n_valid = jnp.asarray([0, c, 5, c], jnp.int32)
    kw = {"scale": 0.125}
    if window is not None:
        kw["window"] = window
    if sink:        # from far under the scores to over them
        kw["sinks"] = jnp.asarray(rng.standard_normal(h) * 4, jnp.float32)
    tail = (bt, starts, n_valid)
    if selected:    # about half of the context a row
        tail += (jnp.asarray(rng.random((s, c, mp * ps)) < 0.5,
                             jnp.float32),)
    name = "sparse_paged_prefill" if selected else "ragged_paged_prefill"
    if pool in ("bf16", "f32"):
        k64, v64 = (np.asarray(p.astype(jnp.float32), np.float64)
                    for p in (kp, vp))
        if pool == "f32":       # the same values, multiplied at HIGHEST
            kp, vp = kp.astype(jnp.float32), vp.astype(jnp.float32)
        return name, (q, kp, vp, *tail), kw, k64, v64
    from paddle_tpu.serving.paged_cache import quantize_kv
    kq, ks = quantize_kv(kp.astype(jnp.float32), (2,))
    vq, vs = quantize_kv(vp.astype(jnp.float32), (2,))
    k64, v64 = (np.asarray(p, np.float64)
                * np.asarray(sc, np.float64)[:, :, None]
                for p, sc in ((kq, ks), (vq, vs)))
    return name + "_int8", (q, kq, vq, ks, vs, *tail), kw, k64, v64


def _prefill_reference64(case, args, kw, k64, v64):
    """The chunk's attention in float64 on the stored values: causal,
    inside the window, under the selection, a sink's term in the
    denominator; dead rows zeros."""
    h, kv, dk, dv, c, ps, mp, window, sink, selected = _PREFILL_FOLDS[case]
    q = np.asarray(args[0].astype(jnp.float32), np.float64)
    tail = args[-4:] if selected else args[-3:]
    bt, starts, n_valid = (np.asarray(a) for a in tail[:3])
    sel = np.asarray(tail[3]) > 0 if selected else None
    sinks = np.asarray(kw["sinks"], np.float64) if sink else None
    out = np.zeros(q.shape[:-1] + (dv,))
    tok = np.arange(mp * ps)
    for sl in range(q.shape[0]):
        k = k64[bt[sl]].reshape(-1, kv, dk)
        v = v64[bt[sl]].reshape(-1, kv, dv)
        pos = starts[sl] + np.arange(c)
        ok = (tok[None] <= pos[:, None]) & (np.arange(c) < n_valid[sl])[:, None]
        if window is not None:
            ok &= tok[None] > pos[:, None] - window
        if selected:
            ok &= sel[sl]
        for hh in range(h):
            g = hh // (h // kv)
            sc = np.where(ok, q[sl, :, hh] @ k[:, g].T * kw["scale"], -np.inf)
            top = sc.max(axis=1, keepdims=True)
            if sink:
                top = np.maximum(top, sinks[hh])
            top = np.where(np.isfinite(top), top, 0.0)
            e = np.exp(sc - top)
            denom = e.sum(axis=1, keepdims=True)
            if sink:
                denom = denom + np.exp(sinks[hh] - top)
            out[sl, :, hh] = np.where(
                denom > 0, e / np.where(denom > 0, denom, 1.0), 0.0) @ v[:, g]
        out[sl, n_valid[sl]:] = 0.0
    return out


class TestPrefillFoldsOperandsAsStored:
    TOL = 2e-5

    def _run(self, name, args, kw, pb=2):
        return np.asarray(kernels.dispatch(
            name, *args, impl="pallas_interpret",
            block_sizes={"pages_per_block": pb}, **kw).astype(jnp.float32))

    @pytest.mark.parametrize("q_dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("case, pool", _PREFILL_POOLS)
    def test_against_float64_on_the_stored_values(self, case, pool, q_dtype):
        """float32 queries: the float32 output within 2e-5 of float64.
        bf16 queries: the bf16 output is the rounding of a value that
        close (half a unit in its last place, at most 2**-8 of the value)."""
        name, args, kw, k64, v64 = _prefill_fold_sample(
            case, pool, jnp.dtype(q_dtype))
        want = _prefill_reference64(case, args, kw, k64, v64)
        got = self._run(name, args, kw)
        assert np.abs(want).max() > 0.1
        rounding = 0.0 if q_dtype == "float32" else 2.0 ** -8
        assert (np.abs(got - want)
                <= self.TOL + (self.TOL + rounding) * np.abs(want)).all()
        # the dead slot, and the rows past a chunk's live ones: zeros
        assert not got[0].any() and not got[2, 5:].any()

    @pytest.mark.parametrize("case, pool", [
        cp for cp in _PREFILL_POOLS if cp[1] != "f32"])
    def test_pages_per_block_bit_equal(self, case, pool):
        """A page is one update whatever the block: the order of every
        sum is the setting's at 1."""
        name, args, kw, _k, _v = _prefill_fold_sample(case, pool,
                                                      jnp.float32)
        outs = [self._run(name, args, kw, pb) for pb in (1, 2, 4)]
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)

    @pytest.mark.parametrize("case", sorted(_PREFILL_FOLDS))
    def test_one_bf16_term_of_p_is_seen_and_fails(self, case, monkeypatch):
        """The control of the decode battery, on both prefill folds: `P`
        (and a float32 `q`) rounded to ONE bf16 term misses the tolerance
        the three-term fold sits well inside."""
        from paddle_tpu.serving import decode_attention as DA
        name, args, kw, k64, v64 = _prefill_fold_sample(case, "bf16",
                                                        jnp.float32)
        want = _prefill_reference64(case, args, kw, k64, v64)
        split_err = np.abs(self._run(name, args, kw) - want).max()
        monkeypatch.setattr(DA, "_bf16_terms",
                            lambda x: (x.astype(jnp.bfloat16),))
        DA._paged_attend_pallas.clear_cache()        # traced with the split
        try:
            rounded_err = np.abs(self._run(name, args, kw) - want).max()
        finally:
            DA._paged_attend_pallas.clear_cache()
        assert split_err < self.TOL / 4
        assert rounded_err > self.TOL * 20

    @pytest.mark.parametrize("case, pool, fold, stack", [
        ("mha-12x64", "bf16", "head", True),
        ("mha-12x64", "int8", "head", True),
        ("mha-12x64", "f32", "head", True),
        ("selected-8-over-2x64", "bf16", "head", False),
        ("gqa-32-over-4x128", "bf16", "group", False),
        ("wide-keys-sink-window", "f32", "group", False)])
    def test_the_form_is_read_off_shapes_and_dtypes(self, case, pool, fold,
                                                    stack):
        """One place decides how a call folds (by head or by KV head),
        whether its operands go to the MXU as stored, and whether three
        terms fit the MXU's rows stacked; the battery above runs every
        combination it names."""
        from paddle_tpu.serving import decode_attention as DA
        h, kv, dk, dv, c, _ps, _mp, _w, _sink, selected = _PREFILL_FOLDS[case]
        dtype = {"bf16": jnp.bfloat16, "int8": jnp.int8,
                 "f32": jnp.float32}[pool]
        form = DA._prefill_fold_form(h, c, kv, dk, dv, jnp.dtype(dtype),
                                     selected)
        assert form == (fold == "group", pool != "f32", stack)


def _sparse_decode_cell_args():
    """``sparse_paged_decode`` at the docs cell's geometry: 32 slots, 32
    query heads over 4 KV heads of 128, tables of 128 pages of a
    1280-page bf16 pool, the selection as a mask, groups of 8."""
    sds = jax.ShapeDtypeStruct
    pages = sds((1280, 128, 4 * 128), jnp.bfloat16)
    return (sds((32, 32, 128), jnp.bfloat16), pages, pages,
            sds((32, 128), jnp.int32), sds((32, 128 * 128), jnp.float32),
            sds((32,), jnp.int32), sds((16, 8), jnp.int32),
            sds((16,), jnp.int32), sds((32,), jnp.int32))


def test_sparse_decode_traces_to_the_call_it_was():
    """``sparse_paged_decode`` at the docs cell's geometry traces, on its
    Pallas path, to the jaxpr it had when the body that walks the pools
    under the selection was written (PR 44; sha256 taken by these lines
    under this suite's conftest, source positions stripped; a change that
    means to alter the sparse decode call takes it anew): two calls
    under the kernel's one name, blocks of 8 pages."""
    import functools
    import hashlib
    import re
    spec = kernels.get("sparse_paged_decode")
    args = _sparse_decode_cell_args()
    blocks = autotune.static_prior(spec, args, {})
    assert blocks == {"pages_per_block": 8}
    text = str(jax.make_jaxpr(functools.partial(
        spec.pallas_fn, block_sizes=blocks, interpret=False))(*args))
    assert text.count("name=sparse_paged_decode") == 2
    text = re.sub(r" at [^\s\]]+:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "8df1db902acdd374b53cf4fe8056cd7d"
        "f6da3892bab925851d219babfdae37be")


def test_sparse_decode_vmem_estimate_at_the_published_widths():
    """A group's step at 8 members x 8 rows a KV head and blocks of 8
    pages: more than its four buffers of 8 pages (4 MB), under half the
    chip's 16 MiB default scope."""
    spec = kernels.get("sparse_paged_decode")
    need = spec.vmem_estimate(_sparse_decode_cell_args(), {},
                              {"pages_per_block": 8})
    assert 4 * 8 * 128 * 512 * 2 < need < 8 << 20

"""The seam between the serving engine, the page cache and a layer's kind
of attention (``paddle_tpu/serving/layer_kinds.py``).

A toy kind stands in for the dense one by substituting the ONE function
that builds kinds; the engine and the cache must then take its shapes, its
writes, its attention and its counts without naming it. And the arrows
point one way: the engine imports no attention module, the kinds import
neither the engine nor a model.
"""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import decode_attention as DA
from paddle_tpu.serving import layer_kinds
from paddle_tpu.serving.program import ServingSpec

SERVING = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu", "serving")


class Negated(layer_kinds.Paged):
    """K and V as the dense kind lays them out but stored NEGATED (and
    negated back where they are read), beside a tally of the writes a row
    took: ``(num_pages, page_size)`` int32, a third pool array no other
    kind has. It counts the tokens its queries attended to on the device
    and its decode rounds on the host."""

    stat_names = ("toy_attended_tokens",)

    def __init__(self, geo, layers):
        super().__init__(geo, layers)
        self.pools += (
            ((geo.num_pages, geo.page_size), jnp.int32, ()),)

    def write(self, ent, rows, place):
        k, v = super().write(ent[:2], tuple(-r for r in rows), place)
        return k, v, ent[2].at[place[0], place[1]].add(1)

    def attend_decode(self, q, ent, place, index, groups):
        lengths = place[3] + 1
        return DA.ragged_paged_decode_attention(
            q, -ent[0], -ent[1], place[2], lengths, impl="lax"), lengths

    def attend_prefill(self, q, ent, place, n_valid, index):
        return DA.ragged_paged_prefill_attention(
            q, -ent[0], -ent[1], place[2], place[3], n_valid, impl="lax")

    def step_counts(self, context, selected):
        return (selected,)

    def bind(self, reg):
        self._c_rounds = reg.counter(
            "serving_toy_decode_rounds_total",
            "decode rounds the toy kind was asked to count").child()

    def count_decode(self, span, block_tables, lengths, dslots, keeps, n,
                     width):
        self._c_rounds.inc()
        return super().count_decode(span, block_tables, lengths, dslots,
                                    keeps, n, width)


def _toy_build(spec, *, num_slots, page_size, num_pages, dtype,
               share_prefix, tp=1, impl="auto", prefill_chunk=None,
               prefill_room=1):
    geo = layer_kinds.Geometry(num_slots, page_size, num_pages,
                               spec.kv_heads, spec.head_dim, dtype, tp, impl)
    return (Negated(geo, spec.num_layers),) * spec.num_layers


def _serve(registry):
    model = GPT(GPTConfig.tiny(num_heads=2, hidden_size=16,
                               max_position=64))
    eng = serving.ServingEngine(
        model, model.init(jax.random.PRNGKey(0)), num_slots=3, page_size=4,
        prefill_chunk=8, decode_block=2, attn_impl="lax", registry=registry)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 90, n).astype(np.int32) for n in (5, 11, 3)]
    return eng, prompts, [list(o) for o in
                          eng.generate_many(prompts, max_new_tokens=6)]


def test_a_toy_kind_serves_in_place_of_the_dense_one(monkeypatch):
    plain, prompts, want = _serve(obs.MetricsRegistry())
    monkeypatch.setattr(layer_kinds, "build", _toy_build)
    reg = obs.MetricsRegistry()
    toy, _, got = _serve(reg)
    assert got == want                                  # greedy parity
    kinds = toy.cache.config.kinds
    assert all(isinstance(k, Negated) for k in kinds) \
        and len({id(k) for k in kinds}) == 1
    # shapes: the cache laid out the kind's third array and reckons with it
    c = toy.cache.config
    assert all(len(ent) == 3 and ent[2].shape == (c.num_pages, c.page_size)
               for ent in toy.cache.pages)
    assert toy.cache.bytes_per_page() == plain.cache.bytes_per_page() \
        + c.num_layers * c.page_size * 4
    toy.cache.check_invariants()
    for mine, theirs in zip(toy.cache.pages, plain.cache.pages):
        # writes: every row landed where the dense kind's did, negated,
        # and each live row was written once (the null page takes the
        # masked lanes' writes)
        np.testing.assert_array_equal(np.asarray(mine[0]),
                                      -np.asarray(theirs[0]))
        np.testing.assert_array_equal(np.asarray(mine[1]),
                                      -np.asarray(theirs[1]))
        tally = np.asarray(mine[2])
        assert set(np.unique(tally[1:])) <= {0, 1}
        # a prompt's tokens, then three blocks of two (the last block's
        # second token is past the budget, inside the reservation)
        assert tally[1:].sum() == sum(len(p) + 6 for p in prompts)
    # counts: the kind's own series on the host, its count on the device
    snap = reg.snapshot()
    assert snap["serving_toy_decode_rounds_total"] \
        == snap["serving_decode_rounds_total"] > 0
    attended = snap["serving_toy_attended_tokens_total"]
    assert attended > 0 and attended % c.num_layers == 0
    assert not [k for k in obs.MetricsRegistry().snapshot() if "toy" in k]


def _imports(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names |= {f"{node.module}.{a.name}" for a in node.names}
            names.add(node.module)
    return names


def test_the_arrows_point_one_way():
    engine = _imports(os.path.join(SERVING, "engine.py"))
    assert not [n for n in engine if "decode_attention" in n
                or "sparse_attention" in n], engine
    kinds = _imports(os.path.join(SERVING, "layer_kinds.py"))
    assert not [n for n in kinds if n.startswith("paddle_tpu.models")
                or n.endswith("serving.engine")
                or n.endswith("paged_cache")], kinds
    cache = _imports(os.path.join(SERVING, "paged_cache.py"))
    assert not [n for n in cache if "attention" in n or "engine" in n], cache


@pytest.mark.parametrize("word", [
    "latent_row", "_latent", "layer_windows", "_window_layers", "_ring",
    "_selects", "_folds", "select_topk", "extra_rows"])
def test_the_engine_names_no_family(word):
    with open(os.path.join(SERVING, "engine.py")) as f:
        assert word not in f.read()


_SPEC = dict(num_layers=2, num_heads=4, vocab_size=8, max_position=64)
_GEO = dict(num_slots=2, page_size=4, num_pages=9)


@pytest.mark.parametrize("fields, dtype, kinds", [
    (dict(kv_heads=2, head_dim=8), jnp.float32,
     [layer_kinds.Paged] * 2),
    (dict(kv_heads=2, head_dim=8), jnp.int8,
     [layer_kinds.PagedInt8] * 2),
    (dict(kv_heads=2, head_dim=8, layer_windows=(8, None)), jnp.float32,
     [layer_kinds.Ring, layer_kinds.Paged]),
    (dict(kv_heads=1, head_dim=24, latent_row=(16, 8)), jnp.float32,
     [layer_kinds.Latent] * 2),
    (dict(kv_heads=2, head_dim=8, extra_rows=(("idx", 4),), select_topk=8),
     jnp.float32, [layer_kinds.Selecting] * 2),
], ids=["float", "int8", "window", "latent", "selecting"])
def test_build_decides_the_kind_of_every_layer(fields, dtype, kinds):
    built = layer_kinds.build(ServingSpec(**_SPEC, **fields), dtype=dtype,
                              share_prefix=False, **_GEO)
    assert [type(k) for k in built] == kinds
    # layers alike share one object, which knows how many it stands for
    for kind in set(built):
        assert kind.layers == built.count(kind)
    # a pool's geometry is the cache's; the page cache takes the kinds
    cache = serving.PagedKVCache(serving.PagedCacheConfig(
        num_layers=2, num_heads=fields["kv_heads"],
        head_dim=fields["head_dim"], dtype=dtype, share_prefix=False,
        kinds=built, **_GEO))
    cache.check_invariants()
    assert [tuple(a.shape for a in ent) for ent in cache.pages] \
        == [tuple(shape for shape, _, _ in k.pools) for k in built]
    assert cache.capacity_bytes() == cache.bytes_per_page() * 8 \
        + cache.bytes_per_slot() * 2


def test_a_kind_has_the_geometry_of_its_own_layers():
    """Full layers of 1 KV head beside window layers of 2, keys of 24
    beside values of 16, a sink on the window layers: two kinds, each with
    pools of its own two widths and bytes counted from them."""
    spec = ServingSpec(**{**_SPEC, "num_layers": 4}, kv_heads=1, head_dim=24,
                       layer_windows=(None, 8, 8, None),
                       layer_kv_heads=(1, 2, 2, 1), value_dim=16,
                       sink_layers=(False, True, True, False))
    built = layer_kinds.build(spec, dtype=jnp.bfloat16, share_prefix=False,
                              prefill_chunk=4, **_GEO)
    full, ring = built[0], built[1]
    assert built == (full, ring, ring, full) and full is not ring
    assert (type(full), type(ring)) == (layer_kinds.Paged, layer_kinds.Ring)
    assert (full.geo.heads, ring.geo.heads) == (1, 2)
    assert (full.sink, ring.sink, full.layers, ring.layers) \
        == (False, True, 2, 2)
    assert [shape for shape, _, _ in full.pools] == [(9, 4, 24), (9, 4, 16)]
    assert [shape for shape, _, _ in ring.pools] == [(7, 4, 48), (7, 4, 32)]
    assert (full.token_bytes, ring.token_bytes) == (80, 160)
    assert (full.page_bytes, ring.page_bytes) == (4 * 80, 0)
    assert ring.slot_bytes == 3 * 4 * 160
    cache = serving.PagedKVCache(serving.PagedCacheConfig(
        num_layers=4, num_heads=1, head_dim=24, dtype=jnp.bfloat16,
        share_prefix=False, kinds=built, **_GEO))
    cache.check_invariants()
    assert cache.capacity_bytes() == 2 * 8 * 320 + 2 * 2 * 1920


@pytest.mark.parametrize("fields", [
    dict(layer_kv_heads=(1, 2)), dict(value_dim=4),
    dict(sink_layers=(True, False))], ids=lambda f: next(iter(f)))
@pytest.mark.parametrize("other, said", [
    (dict(dtype=jnp.int8), "int8 pages"), (dict(tp=2), "tp=2"),
    (dict(share_prefix=True), "prefix sharing")],
    ids=["int8", "tp", "prefix"])
def test_a_layers_own_geometry_is_refused_where_it_does_not_combine(
        fields, other, said):
    spec = ServingSpec(**_SPEC, kv_heads=2, head_dim=8, **fields)
    kw = {**dict(dtype=jnp.float32, share_prefix=False), **other}
    with pytest.raises(ValueError, match=f"{next(iter(fields))}.*{said}"):
        layer_kinds.build(spec, **_GEO, **kw)


def test_a_spec_that_says_what_the_program_says_declares_nothing():
    spec = ServingSpec(**_SPEC, kv_heads=2, head_dim=8,
                       layer_kv_heads=(2, 2), value_dim=8,
                       sink_layers=(False, False))
    assert (spec.layer_kv_heads, spec.value_dim, spec.sink_layers) \
        == ((), None, ())
    with pytest.raises(ValueError, match="layer_kv_heads"):
        ServingSpec(**_SPEC, kv_heads=2, head_dim=8, layer_kv_heads=(2, 3))
    with pytest.raises(ValueError, match="sink_layers"):
        ServingSpec(**_SPEC, kv_heads=2, head_dim=8, sink_layers=(True,))


@pytest.mark.parametrize("window", [None, 8])
def test_prefill_pairs_and_rows_are_what_the_masks_admit(window):
    """``_seen_prefill`` against a count of the mask itself."""
    spec = ServingSpec(**{**_SPEC, "num_layers": 1}, kv_heads=2, head_dim=8,
                       layer_windows=(window,))
    kind = layer_kinds.build(spec, dtype=jnp.float32, share_prefix=False,
                             num_slots=2, page_size=16, num_pages=9)[0]
    starts, ns = np.array([0, 3, 7, 20, 5]), np.array([16, 9, 1, 16, 0])
    pairs = rows = 0
    for start, n in zip(starts, ns):
        seen = set()
        for t in range(start, start + n):
            lo = 0 if window is None else max(t - window + 1, 0)
            pairs += t + 1 - lo
            seen.update(range(lo, t + 1))
        rows += len(seen)
    assert kind._seen_prefill(starts, ns) == (pairs, rows)


# -- a run of one slot's chunks in one call (ISSUE 54) ---------------------------

def _ring(window, page, room, slots=2):
    spec = ServingSpec(**{**_SPEC, "num_layers": 1}, kv_heads=2, head_dim=8,
                       layer_windows=(window,))
    kind, = layer_kinds.build(
        spec, dtype=jnp.float32, share_prefix=False, num_slots=slots,
        page_size=page, num_pages=9, prefill_chunk=page,
        **({} if room is None else {"prefill_room": room}))
    return kind


@pytest.mark.parametrize("window, page, chunk, room", [
    (8, 4, 4, 1), (8, 4, 4, 3), (8, 4, 4, 8), (6, 4, 4, 2), (9, 4, 3, 4),
    (128, 128, 128, 8), (16, 8, 5, 3), (3, 4, 4, 2)])
def test_a_run_as_long_as_the_rings_room_laps_no_row_it_still_reads(
        window, page, chunk, room):
    """The ring's mapping played out token by token: a slot at any length
    gives one call ``room`` chunks; every token the run writes and every
    token its queries read (the window behind each) has a row of the ring
    to itself, so no write of the call lands on a row another query of the
    same call still reads. ``check_run`` says the same, and refuses a run
    one chunk longer somewhere, by name."""
    ring = _ring(window, page, room)
    assert ring.prefill_run == room
    assert ring.ring_pages == -(-window // page) + room
    assert ring.slot_bytes == ring.ring_pages * ring.row_bytes
    for start in range(0, 3 * ring.ring_pages * page, chunk):
        tokens = room * chunk
        ring.check_run(start, tokens)
        span = range(max(start - window + 1, 0), start + tokens)
        rows = {(ring.page_of(1, t // page), t % page) for t in span}
        assert len(rows) == len(span), (start, "two tokens on one row")
        assert all(1 + ring.ring_pages <= p <= 2 * ring.ring_pages
                   for p, _ in rows), (start, "left the slot's ring")
    if chunk == page:
        # one page more than the room: refused wherever the run starts
        # on a page edge, with the numbers that do not fit
        with pytest.raises(ValueError, match=rf"prefill_run={room}\b"):
            ring.check_run(4 * page, (room + 1) * page)


def test_the_rings_tables_are_as_wide_as_a_window_whatever_the_room():
    """Room widens the pool: a prefill lane's table is its own window's
    span from its own first page, a decode token's its window's."""
    for room in (1, 3, 8):
        ring = _ring(8, 4, room)
        lanes = jnp.asarray([1, 1, 2])          # two lanes of slot 0
        starts = jnp.asarray([8, 12, 0])
        positions = starts[:, None] + jnp.arange(4)
        valid = jnp.ones((3, 4), bool)
        under = layer_kinds.under_table(
            jnp.zeros((3, 1), jnp.int32), positions, valid,
            jnp.arange(3)[:, None], 4, starts)
        pages, _off, table, base = ring.place_prefill(
            under, positions, valid, lanes)
        assert table.shape == (3, ring.window_pages + 2)
        # lane 1 goes on where lane 0 ends, in the next page of the ring
        first = np.maximum(np.asarray(starts) - 8 + 1, 0) // 4
        np.testing.assert_array_equal(
            np.asarray(table[:2]),
            1 + (first[:2, None] + np.arange(4)) % ring.ring_pages)
        np.testing.assert_array_equal(np.asarray(base),
                                      np.asarray(starts) - first * 4)
        assert int(pages[1, 0]) == 1 + 3 % ring.ring_pages
        place = ring.place_decode(
            (None, jnp.zeros((2,), jnp.int32), None,
             jnp.asarray([5, 21])), jnp.ones((2,), bool), jnp.arange(2))
        assert place[2].shape == (2, ring.window_pages + 1)


@pytest.mark.parametrize("lanes, room", [
    (None, 1), (1, 1), (2, 2), (4, 4), (8, 8), (32, 8), (16, 8)])
def test_build_gives_a_ring_the_room_the_budget_can_use(lanes, room):
    """The engine owns the one constant: it asks for a step of its lane
    buckets, or the lanes its budget buys if fewer, and ``build`` gives
    the ring what it is asked for (left out: the one page it had)."""
    from paddle_tpu.serving import engine as E
    ring = _ring(8, 4, None if lanes is None else min(lanes, E._LANE_STEP))
    assert (ring.prefill_run, ring.ring_pages) == (room, 2 + room)
    assert not hasattr(layer_kinds, "RING_ROOM") and E._LANE_STEP == 8
    # the pool is the slots' rings and the null page
    assert ring.pools[0][0][0] == 2 * ring.ring_pages + 1


@pytest.mark.parametrize("fields, dtype, run", [
    (dict(), jnp.float32, None),
    (dict(), jnp.int8, 1),
    (dict(kv_heads=1, head_dim=24, latent_row=(16, 8)), jnp.float32, 1),
    (dict(select_topk=8, extra_rows=(("idx", 4),)), jnp.float32, 1),
    (dict(extra_rows=(("idx", 4),)), jnp.float32, 1),
    (dict(layer_windows=(8, None)), jnp.float32, 4)],
    ids=["paged", "int8", "latent", "selecting", "extra_rows", "ring"])
def test_each_kind_says_how_long_a_run_it_takes(fields, dtype, run):
    spec = ServingSpec(**{**_SPEC, **dict(kv_heads=2, head_dim=8), **fields})
    kinds = layer_kinds.build(spec, dtype=dtype, share_prefix=False,
                              num_slots=2, page_size=4, num_pages=9,
                              prefill_chunk=4, prefill_room=4)
    assert kinds[0].prefill_run == run
    assert kinds[-1].prefill_run in (run, None)


def _gpt_engine(**kw):
    model = GPT(GPTConfig.tiny(num_heads=2, hidden_size=16,
                               max_position=64))
    return serving.ServingEngine(
        model, model.init(jax.random.PRNGKey(0)), **{**dict(
            num_slots=4, page_size=4, prefill_chunk=4, prefill_budget=12,
            attn_impl="lax", registry=obs.MetricsRegistry()), **kw})


@pytest.mark.parametrize("how, limit", [
    ("plain", 3), ("prefix_sharing_off", 3), ("int8", 1), ("tp", 1),
    ("prefill_tier", 1), ("speculative", 1), ("one_lane_budget", 1)])
def test_the_engine_takes_the_least_run_of_what_it_is_built_from(how, limit):
    """The run limit follows the program's kinds and the engine's options,
    never an argument: an option not shown to take runs answers 1 and the
    round is the loop it was."""
    kw = {"plain": {}, "prefix_sharing_off": dict(prefix_sharing=False),
          "int8": dict(cache_dtype=jnp.int8), "tp": dict(tp=2),
          "prefill_tier": dict(tier="prefill"),
          "one_lane_budget": dict(prefill_budget=4)}.get(how)
    if how == "speculative":
        draft = GPT(GPTConfig.tiny(num_layers=1, num_heads=2, hidden_size=16,
                                   max_position=64))
        kw = dict(draft_model=draft,
                  draft_params=draft.init(jax.random.PRNGKey(1)), spec_k=2)
    if how == "tp" and len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    eng = _gpt_engine(**kw)
    assert eng._run_limit == limit
    assert eng._lane_cap == (1 if how == "one_lane_budget" else 3)


#: sha256 (16 hex digits) of the lowered text of each tiny program's decode
#: and prefill step AT THE PARENT OF PR 49 (``tests/step_texts.py``, run on
#: a checkout of 89f4e56): a program that declares none of the fields that
#: PR added builds the kinds, pools and step programs it built before
STEPS_BEFORE_A_LAYER_HAD_ITS_OWN_GEOMETRY = {
    "gpt[lax]": ("934684c9a32f713f",
                 "bf5381ee66851d70"),
    "gpt[pallas_interpret]": ("855e0e0dc1357239",
                              "5674d5e1ccaa6201"),
    "latent_conv_moe[lax]": ("91b9896893603f16",
                             "aaab4fb5a3bd25c5"),
    "latent_conv_moe[pallas_interpret]": ("641e8ce19ee86e1c",
                                          "8221b111c837b828"),
    "window_moe[lax]": ("98cbb84e63537930",
                        "5cf3f1c36adb3285"),
    "window_moe[pallas_interpret]": ("50b2307f0e9459b2",
                                     "d326109e034271b5"),
}


@pytest.mark.parametrize("program", sorted(
    STEPS_BEFORE_A_LAYER_HAD_ITS_OWN_GEOMETRY))
def test_the_other_programs_lower_to_the_steps_they_had(program):
    """GPT-2's, ZAYA1's and K-EXAONE's tiny programs: the decode and the
    prefill step lower to the text they lowered to before the paged
    kernels took keys wider than values and a sink (the hashes move with
    the JAX version too: regenerate them on the PARENT commit with
    ``tests/step_texts.py``, never on the change)."""
    import step_texts
    name, impl = program[:-1].split("[")
    got = step_texts.step_hashes(name, impl)
    assert (got["decode"], got["prefill"]) \
        == STEPS_BEFORE_A_LAYER_HAD_ITS_OWN_GEOMETRY[program]


def test_build_refuses_what_does_not_combine():
    def build(dtype=jnp.float32, share_prefix=False, page_size=4, **fields):
        spec = ServingSpec(**_SPEC, kv_heads=2, head_dim=8, **fields)
        return layer_kinds.build(spec, num_slots=2, page_size=page_size,
                                 num_pages=9, dtype=dtype,
                                 share_prefix=share_prefix, prefill_chunk=8)
    with pytest.raises(ValueError, match="multiple of page_size"):
        build(extra_rows=(("idx", 4),), select_topk=6)
    with pytest.raises(ValueError, match="no extra rows"):
        build(dtype=jnp.int8, extra_rows=(("idx", 4),), select_topk=8)
    with pytest.raises(ValueError, match="no slot state"):
        build(dtype=jnp.int8, slot_state=(("s", (2,)),))
    with pytest.raises(ValueError, match="cannot share prefixes"):
        build(share_prefix=True, slot_state=(("s", (2,)),))
    with pytest.raises(ValueError, match="prefill_chunk=8 > page_size=4"):
        build(layer_windows=(8, None))
    assert build(page_size=8, layer_windows=(8, None))

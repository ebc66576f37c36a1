"""Parity suite for the natively batched SAAT engine.

The batched engine must be indistinguishable from the legacy vmap path
(bit-for-bit on doc ids, fp32 tolerance on scores) and from the exhaustive
oracle at a rank-safe rho — for every scatter_impl, including ragged batches
with zero-weight pad terms and budgets past the total posting count.
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exact_rho, exhaustive_search, saat_search, saat_search_vmap
from repro.core.saat import (
    SaatPlan,
    _gather_postings_batched,
    max_segments_per_term,
    saat_plan,
)
from repro.metrics.ir_metrics import rank_overlap

SCATTER_IMPLS = ("jnp", "sort", "pallas")


def _assert_engines_match(index, qt, qw, *, k, rho, impl):
    ms = max_segments_per_term(index)
    b = saat_search(index, qt, qw, k=k, rho=rho, max_segs_per_term=ms, scatter_impl=impl)
    v = saat_search_vmap(index, qt, qw, k=k, rho=rho, max_segs_per_term=ms, scatter_impl=impl)
    np.testing.assert_array_equal(np.asarray(b.doc_ids), np.asarray(v.doc_ids))
    np.testing.assert_allclose(np.asarray(b.scores), np.asarray(v.scores), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(b.postings_processed), np.asarray(v.postings_processed)
    )
    np.testing.assert_array_equal(np.asarray(b.total_postings), np.asarray(v.total_postings))
    return b


@pytest.mark.parametrize("impl", SCATTER_IMPLS)
def test_batched_matches_vmap_budgeted(bm25_index, bm25_queries, impl):
    qt, qw = bm25_queries
    _assert_engines_match(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10, rho=500, impl=impl
    )


@pytest.mark.parametrize("impl", SCATTER_IMPLS)
def test_batched_matches_vmap_and_exhaustive_at_exact_rho(bm25_index, bm25_queries, impl):
    qt, qw = bm25_queries
    qt, qw = jnp.asarray(qt), jnp.asarray(qw)
    b = _assert_engines_match(
        bm25_index, qt, qw, k=10, rho=exact_rho(bm25_index), impl=impl
    )
    ex = exhaustive_search(bm25_index, qt, qw, k=10)
    np.testing.assert_allclose(np.asarray(b.scores), np.asarray(ex.scores), rtol=1e-3, atol=1e-3)
    assert rank_overlap(np.asarray(b.doc_ids), np.asarray(ex.doc_ids), 10) > 0.99


@pytest.mark.parametrize("impl", SCATTER_IMPLS)
def test_batched_rho_beyond_total_postings(bm25_index, bm25_queries, impl):
    """A budget past every query's postings must stop at each query's total."""
    qt, qw = bm25_queries
    qt, qw = jnp.asarray(qt[:6]), jnp.asarray(qw[:6])
    rho = exact_rho(bm25_index) * 2
    b = _assert_engines_match(bm25_index, qt, qw, k=10, rho=rho, impl=impl)
    assert (
        np.asarray(b.postings_processed) == np.asarray(b.total_postings)
    ).all()


@pytest.mark.parametrize("impl", SCATTER_IMPLS)
def test_batched_ragged_batch_with_pad_terms(bm25_index, bm25_queries, impl):
    """Rows with mostly zero-weight pad terms ride the same executable."""
    qt, qw = bm25_queries
    qt, qw = np.array(qt[:8]), np.array(qw[:8])
    # make the batch ragged: progressively zero out trailing terms per row
    for i in range(qt.shape[0]):
        keep = max(1, qt.shape[1] - i)
        qw[i, keep:] = 0.0
        qt[i, keep:] = bm25_index.n_terms  # pad slot
    b = _assert_engines_match(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10, rho=2000, impl=impl
    )
    # shorter queries have fewer candidate postings
    totals = np.asarray(b.total_postings)
    assert totals[-1] <= totals[0]


@pytest.mark.parametrize("impl", SCATTER_IMPLS)
def test_batched_all_pad_query_row(bm25_index, bm25_queries, impl):
    """An all-zero-weight row must produce empty results, not garbage."""
    qt, qw = bm25_queries
    qt, qw = np.array(qt[:4]), np.array(qw[:4])
    qw[2] = 0.0
    qt[2] = bm25_index.n_terms
    b = _assert_engines_match(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10, rho=1000, impl=impl
    )
    assert int(np.asarray(b.total_postings)[2]) == 0
    assert int(np.asarray(b.postings_processed)[2]) == 0
    np.testing.assert_allclose(np.asarray(b.scores)[2], 0.0)


def test_batched_batch_of_one(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    _assert_engines_match(
        bm25_index, jnp.asarray(qt[:1]), jnp.asarray(qw[:1]), k=5, rho=300, impl="jnp"
    )


def test_saat_search_rejects_unbatched_input(bm25_index, bm25_queries):
    qt, qw = bm25_queries
    with pytest.raises(ValueError, match="B, Lq"):
        saat_search(
            bm25_index,
            jnp.asarray(qt[0]),
            jnp.asarray(qw[0]),
            k=5,
            rho=100,
            max_segs_per_term=max_segments_per_term(bm25_index),
        )


def test_batched_plan_matches_single_query_plans(bm25_index, bm25_queries):
    """saat_plan on [B, Lq] == stacking B single-query plans."""
    qt, qw = bm25_queries
    qt, qw = jnp.asarray(qt[:5]), jnp.asarray(qw[:5])
    ms = max_segments_per_term(bm25_index)
    batched = saat_plan(bm25_index, qt, qw, ms)
    for i in range(qt.shape[0]):
        single = saat_plan(bm25_index, qt[i], qw[i], ms)
        np.testing.assert_array_equal(np.asarray(batched.starts[i]), np.asarray(single.starts))
        np.testing.assert_array_equal(np.asarray(batched.cum_len[i]), np.asarray(single.cum_len))
        np.testing.assert_allclose(
            np.asarray(batched.contribs[i]), np.asarray(single.contribs)
        )


def test_max_segments_cached_without_device_sync(bm25_index):
    assert bm25_index.max_segs > 0
    assert max_segments_per_term(bm25_index) == bm25_index.max_segs
    assert bm25_index.max_segs == int(np.asarray(bm25_index.term_seg_count).max())


def _take_along_axis_gather(doc_ids, plan, rho):
    """The slot -> posting map as per-slot ``take_along_axis`` gathers of the
    plan: the row-wise ``searchsorted`` of the slots over ``cum_len`` by a
    histogram of its entries, then the entry's start, its predecessor's
    ``cum_len`` and its contribution read at every slot."""
    B, n_cand = plan.cum_len.shape
    rows = jnp.arange(B)[:, None]
    hist = jnp.zeros((B, rho + 1), jnp.int32).at[rows, jnp.clip(plan.cum_len, 0, rho)].add(1)
    j = jnp.minimum(jnp.cumsum(hist[:, :rho], axis=-1), n_cand - 1)
    p = jnp.broadcast_to(jnp.arange(rho, dtype=jnp.int32), (B, rho))
    prev_cum = jnp.take_along_axis(plan.cum_len, jnp.maximum(j - 1, 0), axis=-1)
    pidx = jnp.take_along_axis(plan.starts, j, axis=-1) + p - jnp.where(j > 0, prev_cum, 0)
    valid = p < plan.total_postings[:, None]
    docs = jnp.where(valid, doc_ids[jnp.where(valid, pidx, 0)], 0)
    contribs = jnp.where(valid, jnp.take_along_axis(plan.contribs, j, axis=-1), 0.0)
    return docs, contribs


# Hand-made plans: segment lengths per row (one row per list), how the
# contributions are drawn, and the budget.
_PLAN_CASES = {
    "zero_len_first_middle_last": ([[0, 3, 0, 5, 0, 2, 0], [0, 0, 4, 1, 0, 0, 6]], "random", 64),
    "pad_row": ([[3, 7, 2, 9], [0, 0, 0, 0], [5, 0, 1, 4]], "random", 128),
    "total_below_rho": ([[4, 2, 6], [1, 1, 1]], "random", 200),
    "total_above_rho_entries_past_budget": (
        [[40, 30, 50, 20, 60, 0, 10], [100, 90, 1, 1, 0, 3, 80]], "random", 100),
    "total_at_rho": ([[60, 40, 28], [128, 0, 0]], "random", 128),
    "single_entry": ([[17], [0], [300]], "random", 256),
    "equal_contribs": ([[5, 0, 9, 3, 11], [2, 2, 2, 2, 2]], "equal", 64),
    "signed_zero_and_negative": ([[3, 4, 0, 5], [6, 0, 7, 2]], "signed", 200),
}


@pytest.mark.parametrize("case", sorted(_PLAN_CASES))
def test_slot_expansion_matches_take_along_axis_gathers(case):
    """The delta scatter and prefix sum give every slot the doc id and the
    contribution *bits* that per-slot gathers of the plan give."""
    lens, contrib_mode, rho = _PLAN_CASES[case]
    lens = np.asarray(lens, np.int32)
    rng = np.random.default_rng(sorted(_PLAN_CASES).index(case))
    n_post = 4096
    # zero-length entries carry a start and a contribution too: no slot may see them
    starts = rng.integers(0, n_post - lens.max() + 1, lens.shape)
    if contrib_mode == "equal":
        contribs = np.full(lens.shape, 0.1, np.float32)
    elif contrib_mode == "signed":
        contribs = rng.choice(np.array([0.0, -0.0, -1.5, 3.25e-8], np.float32), lens.shape)
    else:
        contribs = rng.random(lens.shape, dtype=np.float32) * 40.0
    cum = np.cumsum(lens, axis=-1, dtype=np.int32)
    plan = SaatPlan(
        starts=jnp.asarray(starts, jnp.int32), contribs=jnp.asarray(contribs),
        cum_len=jnp.asarray(cum), total_postings=jnp.asarray(cum[:, -1]),
    )
    doc_ids = jnp.asarray(rng.permutation(n_post).astype(np.int32))
    docs, got, n_proc = jax.jit(
        lambda d, pl: _gather_postings_batched(SimpleNamespace(doc_ids=d), pl, rho)
    )(doc_ids, plan)
    want_docs, want = _take_along_axis_gather(doc_ids, plan, rho)
    np.testing.assert_array_equal(np.asarray(docs), np.asarray(want_docs))
    np.testing.assert_array_equal(
        np.asarray(got).view(np.int32), np.asarray(want).view(np.int32)
    )
    np.testing.assert_array_equal(np.asarray(n_proc), np.minimum(cum[:, -1], rho))


SAAT_PHASES = {"saat.plan", "saat.slots", "saat.gather", "saat.select"}
_TRIVIAL_OPS = {"parameter", "constant", "tuple", "get-tuple-element"}


def _entry_scopes(hlo_text: str) -> list[tuple[str, str, set]]:
    """(instruction, opcode, its saat.* scopes) for each non-trivial
    instruction of a module's entry computation that carries an op_name
    (``None`` in place of the set where it carries none)."""
    import re

    entry = hlo_text[hlo_text.index("\nENTRY"):]
    entry = entry[: entry.index("\n}")]
    out = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\((%?[\w.\-]*)", line)
        if m is None or m.group(2) in _TRIVIAL_OPS:
            continue
        if m.group(2) == "broadcast" and m.group(3).lstrip("%").startswith("constant"):
            continue  # a splat of a literal
        op = re.search(r'op_name="([^"]*)"', line)
        scopes = None if op is None else {p for p in op.group(1).split("/") if p in SAAT_PHASES}
        out.append((m.group(1), m.group(2), scopes))
    return out


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_every_saat_op_lies_in_one_phase_scope(bm25_index, bm25_queries, fused):
    """A profiler trace names the SAAT step's device time by phase from the
    ``saat.*`` scope on each operation's metadata. Every operation the engine
    emits lies in exactly one of the four phases, and every phase has some;
    after the compiler's fusions, every entry instruction that keeps a name
    keeps its phase."""
    qt, qw = bm25_queries
    lowered = saat_search.lower(
        bm25_index, jnp.asarray(qt[:4]), jnp.asarray(qw[:4]), k=10, rho=500,
        max_segs_per_term=max_segments_per_term(bm25_index), scatter_impl="sort",
        fused_topk=fused,
    )
    emitted = _entry_scopes(lowered.as_text(dialect="hlo", debug_info=True))
    assert [(n, op) for n, op, s in emitted if s is None or len(s) != 1] == []
    assert set().union(*(s for _, _, s in emitted)) == SAAT_PHASES
    compiled = _entry_scopes(lowered.compile().as_text())
    assert [(n, op) for n, op, s in compiled if s is not None and len(s) != 1] == []
    assert set().union(*(s for _, _, s in compiled if s)) == SAAT_PHASES

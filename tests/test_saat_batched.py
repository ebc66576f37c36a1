"""Parity suite for the natively batched SAAT engine.

The batched engine must be indistinguishable from the legacy vmap path
(bit-for-bit on doc ids, fp32 tolerance on scores) and from the exhaustive
oracle at a rank-safe rho — for every scatter_impl, including ragged batches
with zero-weight pad terms and budgets past the total posting count.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exact_rho, exhaustive_search, saat_search, saat_search_vmap
from repro.core.saat import max_segments_per_term, saat_plan
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

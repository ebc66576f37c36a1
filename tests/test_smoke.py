"""``chip_smoke.py``'s phases at a tiny size on the CPU, so the script cannot
rot between chip runs.

The phases run with the kernels interpreted — the CPU backend's mode, which
the first test pins down — and keep every parity assertion of the chip run:
each kernel mode's ids equal its oracle's, the admission-queue replay
completes every request, and the sharded step matches the unsharded oracle.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.common import interpret_default
from repro.launch import compile_cache
from repro.launch.smoke import TINY, TINY_SHARDED, run, run_sharded

ROOT = Path(__file__).resolve().parents[1]


def test_kernels_interpret_on_cpu_backend():
    assert jax.default_backend() == "cpu"
    assert interpret_default() is True


@pytest.mark.parametrize("backend", ["gpu", "METAL"])
def test_kernels_refuse_other_backends(monkeypatch, backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match="interpreted only on the CPU backend"):
        interpret_default()


def test_smoke_phases_tiny(capsys):
    run(TINY, seed=0)
    out = capsys.readouterr().out
    parity = [line for line in out.splitlines() if line.startswith("[smoke] parity:")]
    # two treatments x two k x two engines, one batch each
    assert len(parity) == 8 and all("ids_equal=True" in line for line in parity)
    kernel = [line for line in out.splitlines() if "mode=kernel" in line]
    assert len(kernel) == 8 and all("host_witness=True" in line for line in kernel)
    assert "[smoke] queue: requests=12 completed=12" in out


def test_smoke_sharded_tiny(capsys):
    n = min(TINY_SHARDED["n_shards"], jax.device_count())
    run_sharded(dict(TINY_SHARDED, n_shards=n), seed=0)
    out = capsys.readouterr().out
    lines = [line for line in out.splitlines() if line.startswith("[smoke] sharded:")]
    assert len(lines) == 2
    assert all("ids_equal_above_kth=True" in line for line in lines)
    assert all("host_witness=True" in line for line in lines)
    (saat,) = [line for line in lines if "engine=saat" in line]
    assert "ids_equal_unsharded=True" in saat  # SAAT: bit for bit, ties included
    assert "one_shard_per_device=True" in out


def test_host_reference_rejects_wrong_answers():
    from repro.launch.smoke import HostReference

    ref = np.array([[0.0, 3.0, 1.0, 2.0, 2.0]])
    check = HostReference.check
    assert check(ref, np.array([[3.0, 2.0, 2.0]]), np.array([[1, 3, 4]]))
    assert check(ref, np.array([[3.0, 2.0, 2.0]]), np.array([[1, 4, 3]]))  # tie order
    assert not check(ref, np.array([[3.0, 2.0, 1.0]]), np.array([[1, 3, 2]]))  # missed a doc
    assert not check(ref, np.array([[3.0, 2.0, 2.0]]), np.array([[1, 3, 2]]))  # wrong score
    assert not check(ref, np.array([[3.0, 2.0, 2.0]]), np.array([[1, 3, 3]]))  # repeated doc
    # fewer docs reached than k: -inf slots stand for score 0
    assert check(np.array([[0.0, 1.0]]), np.array([[1.0, -np.inf]]), np.array([[1, 7]]))


def test_host_reference_matches_the_engines(bm25_index, bm25_queries):
    """Exhaustive host scores equal the doc-major exhaustive scorer's, and at
    a budget that cuts every query the host's top-k scores equal SAAT's."""
    from repro.core.exhaustive import score_all_docs
    from repro.core.impact_index import query_vector
    from repro.core.saat import max_segments_per_term, saat_search
    from repro.launch.smoke import HostReference

    qt, qw = bm25_queries[0][:4], bm25_queries[1][:4]
    host = HostReference(bm25_index)
    got = host.scores(qt, qw)
    for b in range(4):
        qvec = query_vector(bm25_index, jnp.asarray(qt[b]), jnp.asarray(qw[b]))
        want = np.asarray(score_all_docs(bm25_index, qvec))[: bm25_index.n_docs]
        np.testing.assert_allclose(got[b], want, rtol=1e-5, atol=1e-5)
    rho = 40
    res = saat_search(
        bm25_index, jnp.asarray(qt), jnp.asarray(qw), k=10, rho=rho,
        max_segs_per_term=max_segments_per_term(bm25_index), scatter_impl="sort",
    )
    assert np.all(np.asarray(res.total_postings) > rho)
    budgeted = host.scores(qt, qw, rho)
    assert HostReference.check(budgeted, np.asarray(res.scores), np.asarray(res.doc_ids))
    assert not HostReference.check(got, np.asarray(res.scores), np.asarray(res.doc_ids))


def test_chip_smoke_refuses_without_tpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert not any(line.startswith("{") for line in out.splitlines())


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    was = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = str(ROOT / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    try:
        assert compile_cache.configure_compile_cache() == want
        # the variable, when set, is JAX's own setting: nothing overrides it
        assert jax.config.jax_compilation_cache_dir == (want if env_dir is None else was)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

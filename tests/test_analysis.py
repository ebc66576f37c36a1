"""Seeded-violation tests for ``repro.analysis``: every pass must CATCH.

A static gate that never fires is decoration. Each checker here is fed (a)
the real checked-in registry, which must pass clean, and (b) a deliberately
broken artifact of exactly the failure class it gates — a missing DMA wait,
an over-budget VMEM footprint, a ragged block, a host callback under the
trace — which must produce an actionable violation.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import jaxpr_walk
from repro.analysis.check import _probe_index, main as check_main
from repro.analysis.hot_path import check_dtype_discipline, lint_server, lint_trace
from repro.analysis.kernel_contracts import (
    KernelContract,
    ShapeCase,
    all_contracts,
    check_contract,
)
from repro.serving.scheduler import AnytimeServer, ServingConfig

pytestmark = pytest.mark.analysis

_SDS = jax.ShapeDtypeStruct


# --------------------------------------------------------------------------
# the checked-in registry passes clean
# --------------------------------------------------------------------------

CONTRACTS = all_contracts()


def test_every_kernel_package_declares_a_contract():
    assert set(CONTRACTS) == {
        "block_prune", "block_prune_csr", "block_topk", "chunk_step",
        "impact_scatter", "impact_scatter_topk", "sparse_score",
    }


@pytest.mark.parametrize("name", sorted(CONTRACTS))
def test_checked_in_contract_passes(name):
    violations = check_contract(CONTRACTS[name])
    assert violations == [], "\n".join(str(v) for v in violations)


def test_chunk_step_contract_expects_dma():
    # the double-buffer race class only exists because the copies exist;
    # a refactor that silently drops the DMAs must trip expect_dma
    assert CONTRACTS["chunk_step"].expect_dma


def test_csr_prune_contract_expects_scalar_prefetch():
    # the CSR walk only works because the window offsets arrive via scalar
    # prefetch; a refactor that re-densifies would drop the SMEM operands
    assert CONTRACTS["block_prune_csr"].expect_scalar_prefetch
    assert CONTRACTS["block_prune_csr"].expect_dma
    # chunk_step's grid is mixed: only the multi-trip cases prefetch
    assert any(
        c.expect_scalar_prefetch for c in CONTRACTS["chunk_step"].shape_grid
    )


# --------------------------------------------------------------------------
# seeded violation: missing DMA wait (the chunk_step race class)
# --------------------------------------------------------------------------


def _dma_kernel_jaxpr(wait_before_read: bool):
    """A minimal double-buffer-shaped kernel; optionally drop the wait."""

    def kern(src_hbm, o_ref, buf, sem):
        cp = pltpu.make_async_copy(
            src_hbm.at[pl.ds(0, 8), :], buf.at[0], sem.at[0, 0]
        )
        cp.start()
        if wait_before_read:
            cp.wait()
        o_ref[...] = buf[0]

    f = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=_SDS((8, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, 8, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=True,
    )
    jx = jax.make_jaxpr(f)(_SDS((16, 128), jnp.float32))
    (eqn,) = jaxpr_walk.find_pallas_calls(jx.jaxpr)
    return eqn.params["jaxpr"]


def test_missing_dma_wait_is_caught():
    report = jaxpr_walk.check_dma_discipline(_dma_kernel_jaxpr(wait_before_read=False))
    assert report.starts == 1 and report.waits == 0
    assert report.violations, "the seeded race must be flagged"
    text = " ".join(report.violations)
    assert "wait" in text and "slot" in text  # actionable, names the slot


def test_disciplined_dma_is_clean():
    report = jaxpr_walk.check_dma_discipline(_dma_kernel_jaxpr(wait_before_read=True))
    assert report.starts == 1 and report.waits == 1
    assert report.violations == []


# --------------------------------------------------------------------------
# seeded violation: destination-slot reuse across revolving-buffer trips
# --------------------------------------------------------------------------


def _dst_reuse_kernel_jaxpr(wait_between: bool):
    """Two copies into the SAME destination slot on DIFFERENT semaphores —
    the trip-loop revolving-buffer race the multi-trip chunk step could hit
    if a trip re-issued a slot's copy before the previous trip drained it."""

    def kern(src_hbm, o_ref, buf, sem):
        c1 = pltpu.make_async_copy(
            src_hbm.at[pl.ds(0, 8), :], buf.at[0], sem.at[0, 0]
        )
        c2 = pltpu.make_async_copy(
            src_hbm.at[pl.ds(8, 8), :], buf.at[0], sem.at[1, 0]
        )
        c1.start()
        if wait_between:
            c1.wait()
        c2.start()
        c2.wait()
        if not wait_between:
            c1.wait()
        o_ref[...] = buf[0]

    f = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=_SDS((8, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, 8, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=True,
    )
    jx = jax.make_jaxpr(f)(_SDS((16, 128), jnp.float32))
    (eqn,) = jaxpr_walk.find_pallas_calls(jx.jaxpr)
    return eqn.params["jaxpr"]


def test_dst_slot_reuse_is_caught():
    report = jaxpr_walk.check_dma_discipline(_dst_reuse_kernel_jaxpr(wait_between=False))
    assert report.starts == 2
    assert report.violations, "the seeded destination-slot race must be flagged"
    text = " ".join(report.violations)
    assert "destination" in text and "still in flight" in text


def test_dst_slot_reuse_with_wait_is_clean():
    report = jaxpr_walk.check_dma_discipline(_dst_reuse_kernel_jaxpr(wait_between=True))
    assert report.starts == 2 and report.waits == 2
    assert report.violations == []


# --------------------------------------------------------------------------
# seeded violation: scalar prefetch expected but absent
# --------------------------------------------------------------------------


def test_expect_scalar_prefetch_without_prefetch_is_caught():
    no_sp = KernelContract(
        name="seeded_no_scalar_prefetch",
        make_call=_blocked_op,
        expect_scalar_prefetch=True,
        shape_grid=(ShapeCase("aligned", dict(n=128, blk=64)),),
    )
    violations = check_contract(no_sp)
    assert any(v.check == "scalar_prefetch" for v in violations)


def test_scalar_prefetch_expectation_per_case_override():
    # contract-level default False, one case opting in: only that case fires
    mixed = KernelContract(
        name="seeded_mixed_scalar_prefetch",
        make_call=_blocked_op,
        shape_grid=(
            ShapeCase("plain", dict(n=128, blk=64)),
            ShapeCase(
                "wants_prefetch", dict(n=128, blk=64),
                expect_scalar_prefetch=True,
            ),
        ),
    )
    violations = check_contract(mixed)
    sp = [v for v in violations if v.check == "scalar_prefetch"]
    assert [v.case for v in sp] == ["wants_prefetch"]


# --------------------------------------------------------------------------
# seeded violation: the densified [B, Lq, n_blocks] intermediate
# --------------------------------------------------------------------------


def test_densified_blockmax_is_caught():
    from repro.analysis.hot_path import check_no_densified_blockmax

    B, lq, nb = 2, 6, 7
    jx = jax.make_jaxpr(
        lambda qw, rows: jnp.einsum("ql,qlb->qb", qw, rows)
    )(_SDS((B, lq), jnp.float32), _SDS((B, lq, nb), jnp.float32))
    violations = check_no_densified_blockmax(jx, (B, lq, nb), "seeded", "dense")
    assert violations, "the densified intermediate must be flagged"
    assert all(v.check == "dense_blockmax" for v in violations)
    assert "block_prune_csr" not in str(violations[0])  # message is generic
    assert "CSR" in str(violations[0])


def test_daat_phase0_gate_is_clean():
    from repro.analysis.check import run_daat_phase0_checks

    assert run_daat_phase0_checks() == []


# --------------------------------------------------------------------------
# seeded violation: VMEM over budget (with per-operand breakdown)
# --------------------------------------------------------------------------


def _copy_op(dims):
    n = dims["n"]

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    f = pl.pallas_call(kern, out_shape=_SDS((n,), jnp.float32), interpret=True)
    return f, (_SDS((n,), jnp.float32),)


def test_vmem_over_budget_is_caught():
    # 8 MiB f32 in + out, x2 pipeline each = 32 MiB against the 16 MiB core
    hog = KernelContract(
        name="seeded_vmem_hog",
        make_call=_copy_op,
        shape_grid=(ShapeCase("huge", dict(n=1 << 21)),),
    )
    violations = check_contract(hog)
    vmem = [v for v in violations if v.check == "vmem"]
    assert vmem, "an over-budget footprint must be flagged"
    assert "breakdown" in vmem[0].message  # names the offending tile
    assert "x2" in vmem[0].message


def test_vmem_within_budget_is_clean():
    small = KernelContract(
        name="seeded_vmem_small",
        make_call=_copy_op,
        shape_grid=(ShapeCase("tiny", dict(n=1024)),),
    )
    assert check_contract(small) == []


# --------------------------------------------------------------------------
# seeded violation: ragged block / missing DMAs where expected
# --------------------------------------------------------------------------


def _blocked_op(dims):
    n, blk = dims["n"], dims["blk"]

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    f = pl.pallas_call(
        kern,
        grid=(-(-n // blk),),
        in_specs=[pl.BlockSpec((blk,), lambda i: (i,))],
        out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
        out_shape=_SDS((n,), jnp.float32),
        interpret=True,
    )
    return f, (_SDS((n,), jnp.float32),)


def test_ragged_block_is_caught():
    ragged = KernelContract(
        name="seeded_ragged",
        make_call=_blocked_op,
        shape_grid=(ShapeCase("ragged", dict(n=100, blk=64)),),
    )
    violations = check_contract(ragged)
    assert any(v.check == "divisibility" for v in violations)


def test_expect_dma_without_copies_is_caught():
    no_dma = KernelContract(
        name="seeded_no_dma",
        make_call=_blocked_op,
        expect_dma=True,
        shape_grid=(ShapeCase("aligned", dict(n=128, blk=64)),),
    )
    violations = check_contract(no_dma)
    assert any(v.check == "dma" for v in violations)


# --------------------------------------------------------------------------
# seeded violation: host callback / weak type on a traced serve step
# --------------------------------------------------------------------------


def test_host_callback_on_hot_path_is_caught():
    def served(qt, qw):
        jax.debug.print("theta={t}", t=qw.sum())  # the classic accident
        return qw * 2

    violations, fp = lint_trace(
        served, (_SDS((2, 4), jnp.int32), _SDS((2, 4), jnp.float32)),
        "seeded", "callback",
    )
    assert fp is not None
    assert any(v.check == "host_sync" for v in violations)
    assert "host-side wrapper" in str(violations[0])  # says where it belongs


def test_weak_type_at_boundary_is_caught():
    jx = jax.make_jaxpr(lambda w: w + 1.0)(1.5)  # python scalar leaks in
    violations = check_dtype_discipline(jx, "seeded", "weak")
    assert any(v.check == "weak_type" for v in violations)


def test_pure_hot_path_is_clean():
    violations, fp = lint_trace(
        lambda qt, qw: (qw * 2.0).sum(-1),
        (_SDS((2, 4), jnp.int32), _SDS((2, 4), jnp.float32)),
        "seeded", "pure",
    )
    assert fp is not None and violations == []


# --------------------------------------------------------------------------
# the real serving grid lints clean; executable keys behave
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def probe_index():
    return _probe_index()


@pytest.mark.parametrize(
    "cfg",
    [
        ServingConfig(engine="saat", k=5, rho_ladder=(200, 1000), lq_buckets=(4, 8)),
        ServingConfig(
            engine="daat", k=5, daat_est_blocks=4, daat_block_budget=4,
            daat_use_kernels=True, lq_buckets=(4,),
        ),
    ],
    ids=["saat", "daat_kernels"],
)
def test_server_grid_lints_clean(probe_index, cfg):
    violations = lint_server(AnytimeServer(probe_index, cfg), batch_sizes=(2,))
    assert violations == [], "\n".join(str(v) for v in violations)


def test_executable_keys_distinguish_configs(probe_index):
    base = dict(k=5, rho_ladder=(200,), lq_buckets=(4,))
    s1 = AnytimeServer(probe_index, ServingConfig(engine="saat", **base))
    s2 = AnytimeServer(probe_index, ServingConfig(engine="saat", fused_topk=True, **base))
    s3 = AnytimeServer(probe_index, ServingConfig(engine="saat", **base))
    assert s1.executable_key(4, 2) != s2.executable_key(4, 2)  # flag forks
    assert s1.executable_key(4, 2) == s3.executable_key(4, 2)  # same config aliases
    assert s1.executable_key(4, 2) != s1.executable_key(8, 2)  # bucket forks
    assert s1.executable_key(4, 2) != s1.executable_key(4, 4)  # batch forks


def test_bucketize_canonicalizes_dtypes(probe_index):
    # i64/f64-ish caller input must not fork the compile cache: _bucketize
    # hands the engine strong i32/f32 regardless of what arrives
    server = AnytimeServer(
        probe_index, ServingConfig(engine="saat", k=5, rho_ladder=(200,), lq_buckets=(4,))
    )
    qt = np.zeros((2, 3), np.int16)
    qw = np.zeros((2, 3), np.float16)
    ct, cw, bucket = server._bucketize(qt, qw)
    assert ct.dtype == jnp.int32 and cw.dtype == jnp.float32
    assert bucket == 4


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------


def test_cli_list(capsys):
    assert check_main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "chunk_step" in out and "expect_dma=True" in out
    assert "block_prune_csr" in out


def test_cli_single_contract(capsys):
    assert check_main(["--contract", "block_prune"]) == 0
    assert "0 violations" in capsys.readouterr().out

"""CPU tests of the benchmark at a tiny size. Run them by path:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests

The Pallas kernels run in interpret mode on the CPU.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

LIMITS = {"topk_gap": 1e-4, "id_gap": 1e-4}


@pytest.fixture
def saat_cfg():
    return dict(name="tiny-saat", treatment="spladev2", n_docs=1024, n_queries=96,
                corpus={"concepts_per_doc": 6.95, "concepts_per_query": 4.0},
                capacity={"postings": 262144, "segments": 196608, "block_max": 98304,
                          "doc_terms": 768, "max_weight": 48.0},
                serving={"engine": "saat", "fused_topk": True}, rho=2048, limits=LIMITS)


@pytest.fixture
def open_trf():
    return dict(loop="open", k=10, lanes=[32, 64], lane_shares=[0.78, 0.22],
                stream_seed=5, rate_qps=150.0, batch_shapes=[8], max_wait_s=0.0,
                deadline_ms=None, degrade_rho=False, check_sample=1000)

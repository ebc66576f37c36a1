"""The doc-sharded set-up on four virtual CPU devices, in a process of its
own (the device count is fixed when JAX starts): a sound run is correct with
one shard of the stacked index on each device, and each fault the sharded
path can have makes ``correct`` false. Also the one-chip index fitting,
pinned to the arrays it gave before the sharded set-up shared it, and the
reader of the cross-chip merge's share of busy time."""
import dataclasses
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness, roofline, trace

CASES = Path(__file__).with_name("sharded_cases.py")

# sha256 of every field of ``harness.build_index`` on the tiny SAAT
# configuration (``conftest.saat_cfg``), as the fitting gave it before
# ``fit_index`` was factored out of it
PINNED_INDEX = {
    3: "da126c52397d15458c81f16c81ad88814b53313483e43ac15be38bd507a65dda",
    2**31 + 11: "da4df5a847f0b2d7d9beebbedb7c30927b813f6a744a115f0639e0ea4fc6b0cb",
}


@pytest.fixture(scope="module")
def sharded_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cases = ["sound", "answer_altered", "half_rows_left_out", "control",
             "shard_offset_shifted", "pool_left_out"]
    done = subprocess.run([sys.executable, str(CASES), *cases], env=env, capture_output=True,
                          text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-4000:]
    runs = {}
    for line in done.stdout.splitlines():
        if line.startswith("{"):
            r = json.loads(line)
            runs[r["case"]] = r
    assert sorted(runs) == sorted(cases), done.stdout[-4000:]
    return runs


def test_sound_sharded_run_is_correct(sharded_runs):
    r = sharded_runs["sound"]
    assert r["correct"], r["checks"]
    assert r["placed"]  # every leaf of the stack: one shard on each device
    assert r["completed"] == r["attempted"] > 40
    assert r["checks"]["topk_gap"][0] < 1e-6 and r["checks"]["bad_ids"][0] == 0
    assert "off_budget" not in r["checks"]  # SAAT's alone
    assert len(r["peak_per_chip"]) == 4


@pytest.mark.parametrize("fault,fails", [
    ("answer_altered", "id_gap"),
    ("half_rows_left_out", "topk_gap"),
    ("control", "topk_gap"),
    ("shard_offset_shifted", "id_gap"),
    ("pool_left_out", "topk_gap"),
])
def test_a_fault_of_the_sharded_path_is_not_correct(sharded_runs, fault, fails):
    r = sharded_runs[fault]
    assert not r["correct"]
    value, limit = r["checks"][fails]
    assert value > 10 * limit


@pytest.mark.parametrize("seed", sorted(PINNED_INDEX))
def test_one_chip_index_is_fitted_as_before(saat_cfg, seed):
    corpus, enc = harness.generate(saat_cfg, seed)
    index = harness.build_index(enc, corpus.n_docs, saat_cfg["capacity"])
    h = hashlib.sha256()
    for f in dataclasses.fields(index):
        v = getattr(index, f.name)
        h.update(f.name.encode())
        h.update(np.ascontiguousarray(np.asarray(v)).tobytes() if hasattr(v, "shape")
                 else repr(v).encode())
    assert h.hexdigest() == PINNED_INDEX[seed]


def test_collective_share_on_a_hand_made_trace():
    ms = 1_000_000
    spans = [("bench.window", 0, 100 * ms)]
    chip0 = [("fusion.1", 0, 40 * ms), ("all-gather.3", 40 * ms, 50 * ms),
             ("all_gather.15", 45 * ms, 55 * ms)]  # overlapping: counted once
    chip1 = [("fusion.1", 0, 30 * ms), ("all-reduce-start.1", 30 * ms, 60 * ms)]
    got = trace.reduce([chip0, chip1], spans)
    # busy: 55 and 60 ms; collectives: 15 and 30 ms; both as the chips' mean
    assert got.busy_s == pytest.approx(0.0575) and got.collective_s == pytest.approx(0.0225)
    run = harness.Run(engine="daat", k=10, rho=None, setup_s=0, setup_phases={}, window_s=0.1,
                      attempted=0, completed=0, peaks=roofline.peaks("TPU v5 lite"), trace=got)
    read = harness.load_metric("collective_share.steady")
    assert read(run) == pytest.approx(100 * 22.5 / 57.5)
    # top ops: each chip's seconds, as the chips' mean
    assert got.device_ops[0] == ["fusion.1", pytest.approx(0.035)]
    # a trace without collectives, or no trace: the reader reads nothing
    run.trace = trace.reduce([[("fusion.1", 0, 40 * ms)]], spans)
    assert run.trace.collective_s is None and read(run) is None
    run.trace = None
    assert read(run) is None

"""The by-phase reduction (perfbench.phases) on a hand-made trace: two
flushes of one executable whose device clock runs 0.95 ms behind the
host's, its operations named through the compiled text of their module,
and the program's serve.* spans inside the benchmark's."""
import pytest

from perfbench import phases

MS = 1_000_000  # ns

MODULE = """HloModule jit_saat_search, is_scheduled=true

%fused_a (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  ROOT %neg.1 = s32[8]{0} negate(%p), metadata={op_name="jit(saat_search)/saat.plan/neg"}
}

%fused_b (p.1: s32[8]) -> s32[8] {
  %p.1 = s32[8]{0} parameter(0)
  %a.1 = s32[8]{0} add(%p.1, %p.1), metadata={op_name="jit(saat_search)/saat.gather/add"}
  %a.2 = s32[8]{0} add(%a.1, %p.1), metadata={op_name="jit(saat_search)/saat.gather/add"}
  %a.3 = s32[8]{0} add(%a.2, %p.1), metadata={op_name="jit(saat_search)/saat.slots/add"}
  ROOT %c.1 = s32[8]{0} copy(%a.3)
}

ENTRY %main (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fused_a
  %sort.1 = s32[8]{0} sort(%fusion.1), dimensions={0}, metadata={op_name="jit(saat_search)/saat.slots/sort"}
  %fusion.2 = s32[8]{0} fusion(%sort.1), kind=kLoop, calls=%fused_b
  %copy.3 = s32[8]{0} copy(%fusion.2)
  ROOT %custom-call.1 = s32[8]{0} custom-call(%copy.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(saat_search)/saat.select/jit(impact_scatter_topk_batched)/pallas_call"}
}
"""
# another executable of the same engine: the same names, other shapes, and
# here other scopes; the join must not take it for the one that ran
OTHER = MODULE.replace("s32[8]", "s32[16]").replace("saat.gather", "saat.select")
RAN = "jit_saat_search(111)"

# one flush's device ops, ms after its execution starts
FLUSH_OPS = [
    ("fusion.1", "fusion", 0.0, 4.0),  # plan: its fusion's root
    ("sort.1", "sort", 4.0, 5.0),  # slots: its own metadata
    ("fusion.2", "fusion", 5.0, 15.0),  # gather: most of its fused instructions
    ("copy.3", "copy", 15.0, 15.5),  # the compiler's own: unscoped
    ("custom-call.1", "custom-call", 15.5, 24.0),  # select
]
SKEW = 0.95  # ms the device's clock runs behind the host's


def _flush(first_op_ms):
    """(ops, execution) of one flush, stamped on the device's clock."""
    t0 = first_op_ms - SKEW
    ops = [((RAN, op, "s32[8]{0}", code), (t0 + s) * MS, (t0 + e) * MS)
           for op, code, s, e in FLUSH_OPS]
    return ops, (RAN, t0 * MS, (t0 + FLUSH_OPS[-1][3]) * MS)


def _device(*first_op_ms):
    flushes = [_flush(t) for t in first_op_ms]
    return [([op for ops, _ in flushes for op in ops], [run for _, run in flushes])]


def _spans(serve: bool):
    ms = [
        ("bench.window", 0, 100, {}),
        ("bench.sleep", 0, 9, {}),
        ("bench.poll", 9, 41, {}),
        ("bench.poll", 41, 81, {}),
        ("bench.sleep", 81, 100, {}),
    ]
    if serve:
        ms += [
            # flush 0: the device starts 0.05 ms after its dispatch begins
            ("serve.flush", 10, 40, {"flush": "0"}),
            ("serve.pad", 10, 11, {}), ("serve.prep", 11, 12, {}),
            ("serve.dispatch", 12, 12.05, {}), ("serve.wait", 12.05, 38, {}),
            ("serve.fetch", 38, 40, {}),
            # flush 1: a slow pad, and the device starts 0.5 ms after dispatch
            ("serve.flush", 41.5, 80, {"flush": "1"}),
            ("serve.pad", 41.5, 50, {}), ("serve.prep", 50, 51.5, {}),
            ("serve.dispatch", 51.5, 52, {}), ("serve.wait", 52, 78, {}),
            ("serve.fetch", 78, 80, {}),
        ]
    return [(n, s * MS, e * MS, tags) for n, s, e, tags in ms]


def test_hlo_scopes_own_root_and_majority():
    assert phases.hlo_scopes(MODULE) == {
        "neg.1": "saat.plan", "a.1": "saat.gather", "a.2": "saat.gather",
        "a.3": "saat.slots", "sort.1": "saat.slots", "custom-call.1": "saat.select",
        "fusion.1": "saat.plan", "fusion.2": "saat.gather",
    }


def test_the_join_takes_the_module_text_whose_heads_ran():
    scopes = phases.module_scopes(_device(12.05), [OTHER, MODULE])
    assert scopes == {RAN: phases.hlo_scopes(MODULE)}


def test_phases_offset_and_innermost_gaps_on_a_skewed_clock():
    devices = _device(12.05, 52.0)
    s = phases.reduce(devices, _spans(serve=True), phases.module_scopes(devices, [OTHER, MODULE]))
    assert s.clock_offset_ms == pytest.approx(-SKEW + 0.05)
    assert s.flushes == 2
    assert s.phase_s == pytest.approx({
        "saat.plan": 2 * 4e-3, "saat.slots": 2 * 1e-3, "saat.gather": 2 * 10e-3,
        "unscoped": 2 * 0.5e-3, "saat.select": 2 * 8.5e-3,
    })
    assert sum(s.phase_s.values()) == pytest.approx(s.busy_s)
    assert s.per_flush_ms("saat.gather") == pytest.approx(10.0)
    # shifted by 0.9 ms: flush 0 runs 12.0 to 36.0, flush 1 51.95 to 75.95
    names = {round(d * 1e3, 2): n for n, d in s.idle_gaps}
    assert names == {
        12.0: "bench.sleep",  # before the first flush: 9 ms asleep, 3 polling
        15.95: "serve.pad",  # between the flushes: the slow pad, not bench.poll
        24.05: "bench.sleep",  # after the last
    }


def test_without_serve_spans_the_phases_read_but_no_flush_or_offset():
    devices = _device(12.05, 52.0)
    s = phases.reduce(devices, _spans(serve=False), phases.module_scopes(devices, [MODULE]))
    assert s.flushes == 0 and s.clock_offset_ms is None
    assert s.per_flush_ms("saat.plan") is None
    assert s.phase_s["saat.plan"] == pytest.approx(8e-3)
    assert {n for n, _ in s.idle_gaps} <= {"bench.poll", "bench.sleep"}


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        phases.reduce(_device(12.05), _spans(serve=True)[1:], {})


@pytest.mark.parametrize("event, want", [
    ("%fusion.12 = s32[8388608]{0:T(1024)S(1)} fusion(s32[30781440]{0:T(1024)} %index_doc_ids.1, "
     "s32[8388608]{0:T(1024)S(1)} %broadcast_clamp_fusion), kind=kLoop, calls=%fused_computation.12",
     ("fusion.12", "s32[8388608]{0:T(1024)S(1)}", "fusion")),
    ("%copy-start = (s32[3012608]{0:T(1024)S(1)}, s32[3012608]{0:T(1024)}, u32[]{:S(2)}) "
     "copy-start(s32[3012608]{0:T(1024)} %index_seg_start.1), cross_program_prefetch_index=0",
     ("copy-start", "(s32[3012608]{0:T(1024)S(1)}, s32[3012608]{0:T(1024)}, u32[]{:S(2)})",
      "copy-start")),
    ("  ROOT %tuple.1 = (f32[8,10]{1,0}, s32[8,10]{1,0}) tuple(%a, %b)",
     ("tuple.1", "(f32[8,10]{1,0}, s32[8,10]{1,0})", "tuple")),
])
def test_head_of_a_tpu_op_event_and_of_an_hlo_line(event, want):
    """A TPU "XLA Ops" event is named by its instruction's text (operands
    typed, no metadata); a compiled module's line by the same head."""
    assert phases.head(event) == want

"""The trace reduction and the roofline byte count, on hand-made inputs."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import xtrace  # noqa: E402
from chipbench.roofline import ingest_bytes, roofline_share  # noqa: E402
from chipbench.xtrace import Op, Span  # noqa: E402


def test_busy_is_the_union_of_overlapping_ops():
    ops = [Op("a", 0, 10), Op("b", 5, 10), Op("c", 30, 5), Op("d", 31, 1)]
    assert xtrace.merged(ops) == [(0, 15), (30, 35)]
    assert xtrace.busy_ns(ops) == 20


def test_clip_keeps_only_the_window():
    ops = [Op("a", 0, 10), Op("b", 20, 10), Op("c", 40, 5)]
    assert xtrace.clip(ops, 5, 25) == [Op("a", 5, 5), Op("b", 20, 5)]


def test_kernel_matched_by_name_and_top_ops():
    ops = [
        Op("fusion", 0, 4),
        Op("fused_ingest_dense", 4, 10),
        Op("fused_ingest_dense_transpose", 20, 6),
        Op("fusion", 30, 2),
    ]
    kernels = xtrace.kernel_ops(ops, "fused_ingest_dense")
    assert [o.start for o in kernels] == [4]
    assert xtrace.top_ops(ops, 2) == [("fused_ingest_dense", 10), ("fusion", 6)]


def test_idle_gaps_named_by_innermost_span():
    ops = [Op("k", 10, 10), Op("k", 60, 10)]
    spans = [
        Span("ingest", 0, 100, {"batch": 7}),
        Span("driver.wait", 25, 30, {}),
    ]
    gaps = xtrace.idle_gaps(ops, 0, 100, spans, n=3)
    assert gaps == [("driver.wait", 40), ("ingest batch 7", 30), ("ingest batch 7", 10)]


def test_within_assigns_ops_to_spans():
    spans = [Span("ingest", 0, 10, {}), Span("ingest", 20, 10, {})]
    ops = [Op("k", 1, 2), Op("k", 12, 1), Op("k", 25, 1), Op("k", 29, 3)]
    assert xtrace.within(ops, spans) == [[Op("k", 1, 2)], [Op("k", 25, 1), Op("k", 29, 3)]]


@pytest.mark.parametrize(
    "rows, arity, emissions, cells, expected",
    [
        (1, 2, 0, 0, 8),
        (131072, 2, 131072, 4 * 2048, 131072 * 8 + 131072 * 8 + 4 * 4 * 2048),
        (16384, 2, 16384 * 3, 8192, 16384 * 8 + 16384 * 24 + 32768),
    ],
)
def test_ingest_bytes(rows, arity, emissions, cells, expected):
    assert ingest_bytes(rows, arity, emissions, cells) == expected


def test_roofline_share():
    # 819 MB at 819 GB/s takes 1 ms; measured 4 ms -> 25 %
    assert roofline_share(819e6, 4e-3, 819e9) == pytest.approx(25.0)


@pytest.mark.parametrize(
    "name, label",
    [
        (
            "%fused_ingest_dense.1 = (s32[32,131072]{1,0:T(8,128)}, s32[512,1]{1,0}) "
            "custom-call(s32[8,131072]{1,0} %pad_select_fusion), custom_call_target=\"tpu_custom_call\"",
            "fused_ingest_dense",
        ),
        ("%reduce = s32[512]{0:T(512)} reduce(s32[512,1]{1,0} %pallas_call.8)", "reduce"),
        ("%copy.15 = s32[32,2]{1,0} copy(s32[32,2]{0,1} %enc__e_col__.1)", "copy"),
        ("fusion.7", "fusion"),
    ],
)
def test_op_label_is_the_instruction_name(name, label):
    assert xtrace.op_label(name) == label
    assert bool(xtrace.kernel_ops([Op(xtrace.op_label(name), 0, 1)], "fused_ingest_dense")) == (
        label == "fused_ingest_dense"
    )

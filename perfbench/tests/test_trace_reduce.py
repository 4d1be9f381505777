"""The trace reduction on hand-built intervals whose busy union, gaps and
idle share are known in advance."""
import pytest

from perfbench import trace_reduce as tr

# overlapping (0-2, 1-3), nested (5-9 holds 6-7), a gap (3-5), a late one
IVS = [(0.0, 2.0), (1.0, 3.0), (5.0, 9.0), (6.0, 7.0), (11.0, 12.0)]


def test_merge_and_busy_union():
    assert tr.merge(IVS) == [(0.0, 3.0), (5.0, 9.0), (11.0, 12.0)]
    assert tr.busy_union(IVS) == pytest.approx(8.0)
    assert tr.busy_union([]) == 0.0
    assert tr.busy_union([(1.0, 1.0)]) == 0.0


def test_gaps_inside_and_at_the_window_edges():
    assert tr.gaps(IVS) == [(3.0, 5.0), (9.0, 11.0)]
    assert tr.gaps(IVS, window=(-1.0, 14.0)) == [
        (-1.0, 0.0), (3.0, 5.0), (9.0, 11.0), (12.0, 14.0)]
    assert tr.gaps([], window=(0.0, 2.0)) == [(0.0, 2.0)]


def test_idle_share():
    assert tr.idle_share(IVS, (0.0, 12.0)) == pytest.approx(1 - 8 / 12)
    assert tr.idle_share(IVS, (0.0, 16.0)) == pytest.approx(0.5)
    # clipped to the window
    assert tr.idle_share(IVS, (1.0, 6.0)) == pytest.approx(1 - 3 / 5)


def test_self_times_take_nested_events_out_of_their_parent():
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "fusion.1"),
              (4.0, 6.0, "fusion.2"), (6.0, 9.0, "while.inner"),
              (7.0, 8.0, "fusion.1"), (12.0, 13.0, "copy")]
    got = tr.self_times(events)
    assert got["while"] == pytest.approx(10 - 3 - 2 - 3)
    assert got["fusion.1"] == pytest.approx(3 + 1)
    assert got["while.inner"] == pytest.approx(2)
    assert got["copy"] == pytest.approx(1)
    # the names add up to the busy time
    assert sum(got.values()) == pytest.approx(
        tr.busy_union((s, e) for s, e, _ in events))


def test_innermost_names_what_the_host_was_doing():
    host = [(0.0, 100.0, "thread"), (10.0, 20.0, "np.asarray"),
            (12.0, 13.0, "json.dumps")]
    assert tr.innermost(host, 12.5) == "json.dumps"
    assert tr.innermost(host, 15.0) == "np.asarray"
    assert tr.innermost(host, 200.0) == "host idle or untraced"


def test_top_orders_and_cuts():
    assert tr.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                         ["c", 2.0]]


def test_short_name_keeps_result_opcode_and_target():
    flash = ('%closed_call.7 = (bf16[12,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, '
             'f32[12,8,2048]{2,1,0:T(8,128)}) custom-call(bf16[12,2048,128]'
             '{2,1,0} %bitcast.593), custom_call_target="tpu_custom_call", '
             'operand_layout_constraints={}')
    assert tr.short_name(flash) == \
        "%closed_call.7 custom-call bf16[12,2048,128] tpu_custom_call"
    fusion = ('%fusion.420 = f32[12,2048,128]{2,1,0:T(8,128)S(1)} fusion('
              'bf16[12,2048,128]{2,1,0} %get-tuple-element.3223), kind=kLoop')
    assert tr.short_name(fusion) == "%fusion.420 fusion f32[12,2048,128]"
    # an operand that is a custom call does not make the event one
    user = ('%bitcast_dynamic-update-slice_fusion.34 = f32[18,1536,6144]{2,1,0} '
            'fusion(f32[18,1536,6144]{2,1,0} %gte.1, bf16[2048,6144]{1,0} '
            '%custom-call.52), kind=kLoop')
    assert tr.short_name(user) == \
        "%bitcast_dynamic-update-slice_fusion.34 fusion f32[18,1536,6144]"
    assert tr.short_name("while") == "while"

"""bench/trace_reduce.py on a small synthetic trace."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import readers, trace_reduce  # noqa: E402

NS = 1e-9
SCAN = ("jit(batch_search)/vmap()/while/body/jit(page_scan)/while/body/"
        "closed_call/pallas_call")


class Event:
    def __init__(self, name, start, end, stats=()):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start
        self.stats = list(stats)


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def profile():
    """One chip: a loop [0, 100] holding three ops, a gap [100, 150], one
    op [150, 200]; the host fetched during the gap."""
    ops = [
        Event("fusion.5", 150, 200),
        Event("while.1", 0, 100),
        Event("fusion.2", 10, 30),
        Event("closed_call.3", 40, 60, [("tf_op", SCAN)]),
        Event("sort.4", 60, 90),
    ]
    host = [
        Event("PjitFunction(batch_search)", 0, 95),
        Event("PageFetcher.__call__", 98, 152),
        Event("marker", 120, 120),
    ]
    return Profile([
        Plane("/host:CPU", [Line("python", host)]),
        Plane("/device:TPU:0", [Line("XLA Modules", [Event("jit", 0, 200)]),
                                Line("XLA Ops", ops)]),
    ])


@pytest.fixture
def trace():
    return trace_reduce.from_profile(profile(), window_s=300 * NS)


def test_reads_chip_ops_sorted_and_host_events_with_a_duration(trace):
    assert len(trace.device) == 1
    assert [e[0] for e in trace.device[0]] == [
        "while.1", "fusion.2", "closed_call.3", "sort.4", "fusion.5"]
    assert trace.device[0][2][3] == SCAN
    assert {e[0] for e in trace.host} == {
        "PjitFunction(batch_search)", "PageFetcher.__call__"}


def test_busy_is_the_union_of_op_intervals(trace):
    assert trace_reduce.merged(trace.device[0]) == [(0, 100), (150, 200)]
    assert trace_reduce.busy_s(trace) == pytest.approx(150 * NS)


def test_self_time_leaves_out_nested_ops(trace):
    st = trace_reduce.self_times(trace.device[0])
    assert st["while.1"] == pytest.approx(30 * NS)
    assert st["sort.4"] == pytest.approx(30 * NS)
    assert sum(st.values()) == pytest.approx(trace_reduce.busy_s(trace))


def test_breakdown_names_ops_and_the_host_work_in_each_gap(trace):
    b = trace_reduce.breakdown(trace)
    assert b["device_ops"][0] == ["fusion.5", pytest.approx(50 * NS)]
    assert len(b["device_ops"]) == 5
    assert b["idle_gaps"] == [["PageFetcher.__call__", pytest.approx(50 * NS)]]


def test_kernel_found_by_scope_when_its_name_says_nothing(trace):
    secs, count = trace_reduce.op_seconds(trace, readers.PAGE_SCAN)
    assert (secs, count) == (pytest.approx(20 * NS), 1)
    assert trace_reduce.op_seconds(trace, r"no_such_kernel") == (0.0, 0)


def test_a_trace_without_a_chip_is_an_error():
    host_only = Profile([Plane("/host:CPU", [Line("python", [])])])
    with pytest.raises(ValueError, match="no TPU plane"):
        trace_reduce.from_profile(host_only, 1.0)


# an XSpace as the TPU profiler writes one: the op's scope sits in its
# event metadata's stats, which jax.profiler.ProfileData does not return
XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 5000 duration_ps: 3000
             stats { metadata_id: 2 int64_value: -7 } }
    events { metadata_id: 3 offset_ps: 9000 duration_ps: 1000 }
  }
  event_metadata { key: 1 value { id: 1 name: "closed_call.14"
    stats { metadata_id: 4 str_value: "%s" }
    stats { metadata_id: 5 double_value: 2.5 } } }
  event_metadata { key: 3 value { id: 3 name: "fusion.2" } }
  stat_metadata { key: 2 value { id: 2 name: "run_id" } }
  stat_metadata { key: 4 value { id: 4 name: "tf_op" } }
  stat_metadata { key: 5 value { id: 5 name: "flops" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000 } }
  event_metadata { key: 1 value { id: 1 name: "PjitFunction(batch_search)" } }
}
""" % SCAN


@pytest.fixture
def xplane(tmp_path):
    from jax.profiler import ProfileData

    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return tmp_path


def test_xspace_keeps_the_stats_of_an_events_metadata(xplane):
    from bench import xspace

    (f,) = xplane.rglob("*.xplane.pb")
    space = xspace.read(str(f))
    assert [p.name for p in space.planes] == ["/device:TPU:0", "/host:CPU"]
    (ops,) = space.planes[0].lines
    first, second = ops.events
    assert (first.name, first.start_ns, first.end_ns) == (
        "closed_call.14", 1005.0, 1008.0)
    assert first.stats == {"tf_op": SCAN, "flops": 2.5, "run_id": -7}
    assert (second.name, second.stats, second.duration_ns) == (
        "fusion.2", {}, 1.0)


def test_read_finds_the_kernel_by_its_metadata_scope(xplane):
    tr = trace_reduce.read(str(xplane), window_s=1e-6)
    assert [e[0] for e in tr.device[0]] == ["closed_call.14", "fusion.2"]
    assert trace_reduce.op_seconds(tr, readers.PAGE_SCAN) == (
        pytest.approx(3 * NS), 1)
    assert tr.host == [("PjitFunction(batch_search)", 1000, 1020)]


def _field(num: int, payload: bytes) -> bytes:
    """One length-delimited protobuf field (short payloads only)."""
    assert len(payload) < 128
    return bytes([num << 3 | 2, len(payload)]) + payload


def _hlo_proto(insts: dict[str, str]) -> bytes:
    """HloProto{hlo_module{computations{instructions{name, metadata{
    op_name}}}}} for {instruction name: op_name}."""
    comp = b"".join(
        _field(2, _field(1, n.encode()) + _field(7, _field(2, s.encode())))
        for n, s in insts.items())
    return _field(1, _field(3, comp))


def test_scope_comes_from_the_running_modules_hlo(tmp_path):
    """Events with no scope stat: the same name means page scan in one
    module and a plain copy in another; the XLA Modules line decides."""
    from jax.profiler import ProfileData

    search = _hlo_proto({"closed_call.14": "jit(batch_search)/while/body/"
                         "jit(page_scan)/while/body/closed_call/pallas_call"})
    other = _hlo_proto({"closed_call.14": "jit(other)/copy"})
    octal = lambda b: "".join(f"\\{c:03o}" for c in b)  # noqa: E731
    txt = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 100000 }}
    events {{ metadata_id: 2 offset_ps: 200000 duration_ps: 100000 }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: 10000 duration_ps: 50000 }}
    events {{ metadata_id: 3 offset_ps: 210000 duration_ps: 20000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_batch_search(7)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_other(9)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "closed_call.14" }} }}
}}
planes {{ id: 2 name: "/host:metadata"
  event_metadata {{ key: 7 value {{ id: 7 name: "jit_batch_search(7)"
    stats {{ metadata_id: 1 bytes_value: "{octal(search)}" }} }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "jit_other(9)"
    stats {{ metadata_id: 1 bytes_value: "{octal(other)}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}
"""
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(txt))
    tr = trace_reduce.read(str(tmp_path), window_s=1e-6)
    assert [e[3] for e in tr.device[0]] == [
        "jit(batch_search)/while/body/jit(page_scan)/while/body/"
        "closed_call/pallas_call", "jit(other)/copy"]
    assert trace_reduce.op_seconds(tr, readers.PAGE_SCAN) == (
        pytest.approx(50 * NS), 1)


def test_ops_are_clipped_to_the_annotated_span():
    """The profiler records past the span the run means: busy time is
    counted inside it only, and the window is its length."""
    p = profile()
    p.planes[0].lines[0].events.append(Event("bench.traced_span", 50, 170))
    tr = trace_reduce.from_profile(p, window_s=1.0, span="bench.traced_span")
    assert tr.window_s == pytest.approx(120 * NS)
    assert trace_reduce.merged(tr.device[0]) == [(50, 100), (150, 170)]
    assert trace_reduce.busy_s(tr) <= tr.window_s
    # fusion.2 [10, 30] lies before the span
    assert "fusion.2" not in [e[0] for e in tr.device[0]]
    assert "bench.traced_span" not in [e[0] for e in tr.host]


def test_a_tpu_ops_name_loses_its_hlo_text():
    assert trace_reduce._short(
        "%fusion.157 = s32[15360]{0:T(1024)S(1)} fusion(s32[64,96] %a), "
        "kind=kCustom") == "fusion.157"
    assert trace_reduce._short("closed_call.3") == "closed_call.3"

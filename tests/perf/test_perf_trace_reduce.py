"""The reduction from trace to numbers: on hand-made events whose
answers are known, and on a piece of a trace recorded on the chip."""
import json
import os

import pytest

import perf_testlib

import trace_reduce as R

OP = "%{} = bf16[4,8]{{1,0}} fusion(bf16[4,8]{{1,0}} %p)"


def _trace(ops, modules, host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": list(host)}]}]}


def test_busy_is_the_union_and_a_while_is_charged_its_own_time_only():
    ops = [["%while.1 = (s32[]) while(%t)", 100, 900],
           [OP.format("fusion.1"), 100, 300],
           [OP.format("fusion.2"), 500, 400],
           [OP.format("copy.3"), 1200, 100]]
    red = R.reduce(_trace(ops, [["jit_a(1)", 100, 900],
                                ["jit_b(2)", 1200, 100]]),
                   window=(0, 1500))
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(1000e-9)
    assert red["window_s"] == pytest.approx(1500e-9)
    assert red["idle_s"] == pytest.approx(500e-9)
    ops_s = dict((k, v) for k, v in red["device_ops"])
    assert ops_s["fusion.2 bf16[4,8]"] == pytest.approx(400e-9)
    assert ops_s["while.1 (s32[]"] == pytest.approx(200e-9)
    assert sum(ops_s.values()) == pytest.approx(red["busy_s"])
    assert red["programs"]["jit_a(1)"] == {"launches": 1.0,
                                           "seconds": 900e-9}
    assert red["idle_gaps"] == [["unattributed", pytest.approx(500e-9)]]


def test_gaps_and_launches_are_attributed_to_what_the_host_was_doing():
    ops = [[OP.format("fusion.1"), 100, 900],
           [OP.format("fusion.2"), 1200, 100]]
    mods = [["jit__unknown(1)", 100, 900], ["jit__unknown(2)", 1200, 100],
            ["jit__unknown(1)", 1450, 500]]      # runs past the window
    samples = [(0, "engine.py:_run"), (150, "engine.py:_dispatch_chunk"),
               (950, "engine.py:evict_lru"),
               (1100, "engine.py:_prefill_paged")]
    red = R.reduce(_trace(ops, mods), window=(0, 1500), samples=samples,
                   host_offset_ns=50)
    gaps = dict((k, v) for k, v in red["idle_gaps"])
    # gap 1000..1200: evict_lru from 1000 to 1150, then prefill
    assert gaps["engine.py:evict_lru"] == pytest.approx(150e-9)
    assert gaps["engine.py:_prefill_paged"] == pytest.approx(250e-9)
    by = red["launches_by_host"]
    assert by["engine.py:_dispatch_chunk"] == {
        "launches": 1, "seconds": pytest.approx(900e-9),
        "programs": {"jit__unknown(1)": {
            "launches": 1, "seconds": pytest.approx(900e-9)}}}
    assert by["engine.py:_prefill_paged"]["launches"] == 1
    assert sum(g["launches"] for g in by.values()) == 2   # whole ones


def test_mean_over_devices_and_window_clipping():
    tr = _trace([[OP.format("fusion.1"), 0, 1000]], [["jit_a(1)", 0, 1000]])
    tr["planes"].insert(1, {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [[OP.format("fusion.1"), 0, 500]]},
        {"name": "XLA Modules", "events": [["jit_a(1)", 0, 500]]}]})
    red = R.reduce(tr, window=(200, 1000))
    assert red["devices"] == 2
    assert red["busy_s_by_device"] == [pytest.approx(800e-9),
                                       pytest.approx(300e-9)]
    assert red["busy_s"] == pytest.approx(550e-9)
    assert R.reduce({"planes": []}) == {"devices": 0}


def test_short_name_keeps_the_hlo_name_and_result_shape():
    assert R.short_name(
        "%convert.49 = f32[4096,16,16,128]{3,2,1,0:T(8,128)} convert("
        "bf16[4096,16,16,128]{3,2,1,0} %x)") == \
        "convert.49 f32[4096,16,16,128]"
    assert R.short_name("jit_step(123)") == "jit_step(123)"


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(perf_testlib.PERF, "recorded", "trace_small.json")
    with open(path) as f:
        return json.load(f)


def test_recorded_chip_trace_reduces_to_sane_numbers(recorded):
    """The events that begin in 0.7 s of ``cgpt1b3-chat-steady`` on a
    TPU v5 lite (see the file's ``origin``), to the end of the last:
    a decode chunk and the prefills around it."""
    red = R.reduce(recorded, window=tuple(recorded["window"]),
                   samples=[tuple(s) for s in recorded["samples"]],
                   host_offset_ns=recorded["host_offset_ns"])
    assert red["devices"] == 1
    assert 0.7 < red["window_s"] < 2.0
    assert 0.5 * red["window_s"] < red["busy_s"] <= red["window_s"]
    assert red["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"],
                                          abs=1e-6)
    assert len(red["device_ops"]) == 10
    secs = [s for _n, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all("[" in n for n, _s in red["device_ops"])
    import perf_harness as H

    run = {"trace": red, "conf": {"engine": {"chunk": 8}}}
    per_step_ms = H.load_reader("decode_step_dev_ms").read(run)
    assert 50 < per_step_ms < 150      # 87 ms in the run it is cut from
    share = H.load_reader("prefill_dev_share_pct").read(run)
    assert 0 < share < 30
    assert sum(s for _n, s in red["idle_gaps"]) == \
        pytest.approx(red["idle_s"], rel=1e-6)


# ---- device time by the name scope the program wrote

def test_scopes_sum_to_the_ops_self_time_and_a_parent_is_charged_its_own():
    """A ``while`` under one scope spans operations of scopes nested in
    it and of another: each is charged its own self time, the table
    sums to the operations' self time, a prefix sums its children at
    any depth, and an operation without a path is unscoped."""
    paths = ["jit(step)/jit(main)/layers/while:",
             "jit(step)/jit(main)/layers/while/body/attn.full/pallas_call:",
             "jit(step)/jit(main)/layers/while/body/moe.experts/dot_general:",
             "jit(step)/layers/while/body/moe.experts/gelu/tanh:",
             "jit(other)/moe.route/top_k:"]
    ops = [["%while.1 = (s32[]) while(%t)", 100, 900, 0],
           [OP.format("closed_call.8"), 100, 300, 1],
           [OP.format("fusion.2"), 500, 200, 2],
           [OP.format("fusion.3"), 700, 200, 3],
           [OP.format("fusion.4"), 1200, 100, 4],
           [OP.format("copy.5"), 1300, 50]]
    tr = _trace(ops, [["jit_step(1)", 100, 900], ["jit_other(2)", 1200, 150]])
    tr["paths"] = paths
    red = R.reduce(tr, window=(0, 1500))
    ns = 1e-9
    assert red["scopes"] == {
        "layers/while/body/attn.full/pallas_call": pytest.approx(300 * ns),
        "layers/while/body/moe.experts/dot_general":
            pytest.approx(200 * ns),
        "layers/while/body/moe.experts/gelu/tanh": pytest.approx(200 * ns),
        "layers/while": pytest.approx(200 * ns),     # 900 less its body
        "moe.route/top_k": pytest.approx(100 * ns),
        R.UNSCOPED: pytest.approx(50 * ns)}
    assert sum(red["scopes"].values()) == pytest.approx(red["ops_self_s"])
    assert red["ops_self_s"] == pytest.approx(red["busy_s"])
    assert sum(s for _n, s in red["device_ops"]) == \
        pytest.approx(red["ops_self_s"])
    run = {"trace": red}
    assert R.scope_seconds(run, "moe.experts") == pytest.approx(400 * ns)
    assert R.scope_seconds(run, "moe.experts/gelu") == \
        pytest.approx(200 * ns)
    assert R.scope_seconds(run, "pallas_call") == pytest.approx(300 * ns)
    assert R.scope_seconds(run, "layers") == pytest.approx(900 * ns)
    assert R.scope_seconds(run, "layers/while/body") == \
        pytest.approx(700 * ns)
    assert R.scope_seconds(run, "moe") is None       # whole components
    assert R.scope_seconds(run, "experts/moe") is None
    assert R.scope_seconds({"trace": None}, "layers") is None
    # clipped to a window, the table still sums to the self time
    clipped = R.reduce(tr, window=(200, 800))
    assert sum(clipped["scopes"].values()) == \
        pytest.approx(clipped["ops_self_s"]) == pytest.approx(600 * ns)
    # the kernel's share of busy time, as its reader takes it
    import perf_harness as H

    assert H.load_reader("attn_kernel_share_pct").read(run) == \
        pytest.approx(100.0 * 300 / 1050)
    assert H.load_reader("attn_kernel_share_pct.sat").read(
        {"trace": {"scopes": {R.UNSCOPED: 1.0}, "busy_s": 1.0}}) is None


def test_the_table_keeps_the_largest_scopes_and_sums_the_rest_as_other():
    paths = [f"jit(f)/s{i}/add:" for i in range(50)]
    ops = [[OP.format(f"fusion.{i}"), 100 * i, 10 + i, i]
           for i in range(50)]
    tr = _trace(ops, [["jit_f(1)", 0, 6000]])
    tr["paths"] = paths
    red = R.reduce(tr, window=(0, 6000))
    assert len(red["scopes"]) == 41 and R.OTHER in red["scopes"]
    assert red["scopes"][R.OTHER] == pytest.approx(
        sum(10 + i for i in range(10)) * 1e-9)
    assert sum(red["scopes"].values()) == pytest.approx(red["ops_self_s"])
    assert R.scope_seconds({"trace": red}, "s49") == \
        pytest.approx(59e-9)
    assert R.scope_seconds({"trace": red}, "s3") is None   # under other
    assert R.scope_key("jit(a)/pjit(b)/x/y:") == "x/y"
    assert R.scope_key("jit(a)/x/jit(gelu)/tanh") == "x/jit(gelu)/tanh"
    assert R.scope_key("") == R.scope_key(None) == R.UNSCOPED


@pytest.mark.parametrize("name,want", [
    ("trace_small", {"busy_s": 1.396135074, "idle_s": 0.022757148,
                     "top": ["while.34 (s32[]", 0.643557047]}),
    ("trace_named", {"busy_s": 1.125875412, "idle_s": 0.055839171,
                     "top": ["while.34 (s32[]", 0.476369535]})])
def test_recordings_without_paths_reduce_as_before(name, want):
    """The recordings of PRs 23 and 24 are in the neutral form that had
    already lost the stats: every number is what the parent of PR 29
    read, and the whole self time lies under the one unscoped key."""
    with open(os.path.join(perf_testlib.PERF, "recorded",
                           name + ".json")) as f:
        rec = json.load(f)
    assert "paths" not in rec
    red = R.reduce(rec, window=tuple(rec["window"]),
                   samples=[tuple(s) for s in rec["samples"]],
                   host_offset_ns=rec["host_offset_ns"])
    assert red["busy_s"] == want["busy_s"]
    assert red["idle_s"] == want["idle_s"]
    assert red["device_ops"][0] == want["top"]
    assert red["scopes"] == {R.UNSCOPED: red["ops_self_s"]}
    assert red["ops_self_s"] == pytest.approx(red["busy_s"])


# ---- the paths are read off the file: a hand-made .xplane.pb

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    """One protobuf field: an int as a varint, bytes or str as
    length-delimited."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xplane_file(tmp_path):
    """An XSpace with one device plane: two programs that share an
    operation's name under different paths, a ``while`` with a body
    operation, an operation without a path; one host plane with the
    sync event. Laid out as tsl/profiler/protobuf/xplane.proto."""
    stats = {1: "tf_op", 2: "program_id", 3: "flops", 4: "a path, interned"}
    stat_meta = b"".join(
        _field(5, _field(1, k) + _field(2, _field(1, k) + _field(2, v)))
        for k, v in stats.items())

    def op(mid, name, program, path=None, ref=None):
        st = _field(5, _field(1, 2) + _field(3, program)) \
            + _field(5, _field(1, 3) + _field(3, 1000))
        if path is not None:
            st += _field(5, _field(1, 1) + _field(5, path))
        if ref is not None:
            st += _field(5, _field(1, 1) + _field(7, ref))
        return _field(4, _field(1, mid) + _field(2, _field(1, mid)
                                                 + _field(2, name) + st))

    shared = OP.format("fusion.1")
    metas = (
        op(1, "%while.9 = (s32[]) while(%t)", 11, "jit(a)/layers/while:")
        + op(2, shared, 11, "jit(a)/layers/while/body/attn/pallas_call:")
        + op(3, shared, 22, "jit(b)/moe.route/top_k:")
        + op(4, OP.format("copy.4"), 22)
        + op(5, OP.format("fusion.5"), 22, ref=4)
        + op(6, "jit_a(11)", 0) + op(7, "jit_b(22)", 0))

    def event(mid, start_ns, dur_ns):
        return _field(4, _field(1, mid) + _field(2, start_ns * 1000)
                      + _field(3, dur_ns * 1000))

    ops_line = _field(3, _field(1, 1) + _field(2, "XLA Ops") + _field(3, 0)
                      + event(1, 100, 900) + event(2, 200, 300)
                      + event(3, 1200, 100) + event(4, 1300, 50)
                      + event(5, 1400, 50))
    mod_line = _field(3, _field(1, 2) + _field(2, "XLA Modules")
                      + _field(3, 0) + event(6, 100, 900)
                      + event(7, 1200, 300))
    device = _field(1, _field(1, 1) + _field(2, "/device:TPU:0")
                    + ops_line + mod_line + metas + stat_meta)
    host = _field(1, _field(1, 2) + _field(2, "/host:CPU") + _field(
        3, _field(1, 1) + _field(2, "python") + _field(3, 0)
        + _field(4, _field(1, 1) + _field(2, 50000) + _field(3, 1000)))
        + _field(4, _field(1, 1) + _field(2, _field(1, 1)
                                          + _field(2, R.SYNC_EVENT))))
    path = os.path.join(str(tmp_path), "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(device + host)
    return path, shared


def test_paths_are_read_from_the_files_metadata_once_per_operation(
        tmp_path):
    path, shared = _xplane_file(tmp_path)
    got = R.op_paths(path)
    assert set(got) == {"/device:TPU:0"}
    assert got["/device:TPU:0"][shared] == {
        11: "jit(a)/layers/while/body/attn/pallas_call:",
        22: "jit(b)/moe.route/top_k:"}
    assert got["/device:TPU:0"][OP.format("fusion.5")] == {
        22: "a path, interned"}              # a stat by reference
    assert OP.format("copy.4") not in got["/device:TPU:0"]
    trace = R.load_xplane(path)
    assert R.sync_event_ns(trace) == 50
    dev = next(p for p in trace["planes"] if p["name"] == "/device:TPU:0")
    ops = next(ln["events"] for ln in dev["lines"]
               if ln["name"] == R.OPS_LINE)
    named = [[ev[0], ev[1], ev[2]] + [trace["paths"][ev[3]]]
             if len(ev) > 3 else ev for ev in ops]
    # the shared name takes the path of the launch that encloses it
    assert named == [
        ["%while.9 = (s32[]) while(%t)", 100, 900, "jit(a)/layers/while:"],
        [shared, 200, 300, "jit(a)/layers/while/body/attn/pallas_call:"],
        [shared, 1200, 100, "jit(b)/moe.route/top_k:"],
        [OP.format("copy.4"), 1300, 50],
        [OP.format("fusion.5"), 1400, 50, "a path, interned"]]
    red = R.reduce(trace)
    assert red["scopes"] == {
        "layers/while": pytest.approx(600e-9),
        "layers/while/body/attn/pallas_call": pytest.approx(300e-9),
        "moe.route/top_k": pytest.approx(100e-9),
        R.UNSCOPED: pytest.approx(50e-9),
        "a path, interned": pytest.approx(50e-9)}
    assert sum(red["scopes"].values()) == pytest.approx(red["ops_self_s"])
    # a cut keeps the paths, so that a recording can be made of it
    piece = R.cut(trace, (150, 1250), [], 0)
    assert piece["paths"] == trace["paths"]
    assert [len(ev) for ln in piece["planes"][0]["lines"]
            if ln["name"] == R.OPS_LINE for ev in ln["events"]] == [4, 4]


def test_recorded_scoped_trace_names_the_kernel_by_its_path():
    """A piece of this PR's traced ``cgpt1b3-chat-steady`` run with the
    paths kept (see the file's ``origin``): the table sums to the
    operations' self time, and the paged-attention kernel is found by a
    path that no fusion numbering enters."""
    path = os.path.join(perf_testlib.PERF, "recorded",
                        "trace_scoped.json")
    assert os.path.getsize(path) < 1_000_000
    with open(path) as f:
        rec = json.load(f)
    assert "builder's chip run, PR 29" in rec["origin"]
    red = R.reduce(rec, window=tuple(rec["window"]),
                   samples=[tuple(s) for s in rec["samples"]],
                   host_offset_ns=rec["host_offset_ns"])
    assert red["devices"] == 1 and 0.3 < red["window_s"] < 0.6
    assert sum(red["scopes"].values()) == \
        pytest.approx(red["ops_self_s"], rel=0.01)
    assert red["ops_self_s"] == pytest.approx(red["busy_s"], rel=0.01)
    kernel = "while/body/closed_call/while/body/closed_call/pallas_call"
    assert max(red["scopes"], key=red["scopes"].get) == kernel
    assert any(p.startswith("jit(decode_chunk_slots_paged)/")
               for p in rec["paths"])          # as the compiler wrote it
    assert not any(k.startswith(("jit(", "pjit(")) for k in red["scopes"])
    run = {"trace": red, "conf": {"engine": {"chunk": 8}}}
    assert R.scope_seconds(run, "pallas_call") == red["scopes"][kernel]
    import perf_harness as H

    share = H.load_reader("attn_kernel_share_pct").read(run)
    assert share == pytest.approx(
        100 * red["scopes"][kernel] / red["busy_s"])
    assert 40 < share < 95      # 79.3 in the run it is cut from
    names = {k.split("(")[0] for k in red["programs"]}
    assert {"jit_decode_chunk_slots_paged",
            "jit_prefill_into_slot_paged"} <= names


# ---- the reduction outside the process that traced (PR 31)

def _handoff_for(name, tmp_path):
    """What a tracing process would hand out for a file: a recording
    (its own window and samples, the marker event added to a copy so
    that the two clocks tie as in a run), or the hand-made
    ``.xplane.pb``."""
    if name == "xplane":
        path, _shared = _xplane_file(tmp_path)
        return {"path": path, "log_dir": "unused", "samples": [
            [10, "engine.py:_run"], [200, "engine.py:_dispatch_chunk"],
            [1150, "engine.py:_prefill_paged"]],
            "t_start": 50, "t_stop": 1500, "sync_host_ns": 0,
            "trace_stop_s": 0.25, "slice_s": 1.45e-6, "launches": 1,
            "ended_by": "launches", "describe": False}
    with open(os.path.join(perf_testlib.PERF, "recorded",
                           name + ".json")) as f:
        rec = json.load(f)
    rec["planes"].append({"name": "/host:CPU", "lines": [
        {"name": "python", "events": [[R.SYNC_EVENT, 7000, 1]]}]})
    path = os.path.join(str(tmp_path), name + "-synced.json")
    with open(path, "w") as f:
        json.dump(rec, f)
    a, b = rec["window"]
    # host clock = trace clock - 7000 + 4000: an offset of 3000
    return {"path": path, "log_dir": "unused",
            "samples": [[t - 3000, lab] for t, lab in rec["samples"]],
            "t_start": a - 3000, "t_stop": b - 3000,
            "sync_host_ns": 4000, "trace_stop_s": 7.5,
            "slice_s": (b - a) / 1e9, "launches": 3,
            "ended_by": "seconds", "describe": False}


@pytest.mark.parametrize("name", ["trace_small", "trace_scoped",
                                  "xplane"])
def test_reduction_in_a_child_gives_the_in_process_dictionary(
        name, tmp_path):
    """``reduce_in_child`` (a serving cell's driver, which imports no
    jax, calls it once the window has closed) runs the same function on
    the same file with the same arguments in a child on the CPU; only
    ``reduce_s`` is the child's own."""
    h = _handoff_for(name, tmp_path)
    here = R.reduce_handoff(h)
    child = R.reduce_in_child(h, str(tmp_path))
    assert child["cost"].pop("reduce_s") > 0
    assert here["cost"].pop("reduce_s") > 0
    assert child == json.loads(json.dumps(here))
    assert here["cost"] == {
        "trace_stop_s": h["trace_stop_s"], "slice_s": h["slice_s"],
        "launches": h["launches"], "ended_by": h["ended_by"],
        "xplane_bytes": os.path.getsize(h["path"]),
        "device_events": sum(len(ln["events"]) for ln in next(
            p for p in R.load_trace(h["path"])["planes"]
            if R.DEVICE_PLANE.match(p["name"]))["lines"])}
    if name != "xplane":
        # and it is the reduction the recording's own tests make
        with open(os.path.join(perf_testlib.PERF, "recorded",
                               name + ".json")) as f:
            rec = json.load(f)
        plain = R.reduce(rec, window=tuple(rec["window"]),
                         samples=[tuple(s) for s in rec["samples"]],
                         host_offset_ns=rec["host_offset_ns"])
        for key, val in plain.items():
            assert here[key] == val, key
    else:
        assert here["devices"] == 1 and here["window_s"] == 1450e-9
        assert here["launches_by_host"]["engine.py:_dispatch_chunk"][
            "launches"] == 1


def test_a_child_that_fails_says_so(tmp_path):
    with pytest.raises(RuntimeError, match="(?s)child exited 1.*No such"):
        R.reduce_in_child({"path": "/nonexistent/x.json"}, str(tmp_path))

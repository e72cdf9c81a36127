"""The program's ``fb.*`` spans: a test-size co-verification session traced
on the CPU, read back with ``bench.spans``; and the span arithmetic and the
existing readers on hand-made traces that hold ``fb.*`` spans."""
import jax
import pytest
from jax.profiler import TraceAnnotation

from bench import common, spans as sp, trace as tr
from repro.core import CongestionConfig, CoVerifySession
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.sweep import flash_backends, flash_firmware
from repro.kernels.systolic_matmul import ops as mm_ops
from repro.kernels.systolic_matmul.sweep import (matmul_backends,
                                                 matmul_firmware)

MM = {"size": 64, "tile": 16}
FL = {"batch": 1, "heads": 2, "seq": 64, "dim": 16, "bq": 16, "bk": 16}
SWEEPS = 2

# (parent, child) on one thread, as core/spans.py's users open them
TREE = {("fb.sweep", "fb.sweep.cells"), ("fb.sweep", "fb.sweep.precheck"),
        ("fb.sweep", "fb.sweep.compare"),
        ("fb.cell", "fb.firmware"), ("fb.cell", "fb.cell.collect"),
        ("fb.firmware", "fb.mem.alloc"), ("fb.firmware", "fb.mem.host_write"),
        ("fb.firmware", "fb.launch"),
        ("fb.launch", "fb.mem.dev_read"), ("fb.launch", "fb.launch.bursts"),
        ("fb.launch", "fb.link"), ("fb.launch", "fb.backend"),
        ("fb.launch", "fb.mem.dev_write"),
        ("fb.mem.dev_read", "fb.link"), ("fb.mem.dev_write", "fb.link"),
        ("bench.window", "bench.sweep"), ("bench.sweep", "fb.sweep")}


def _firmware(fb, op, backend, **cfg):
    fw = matmul_firmware if op == "matmul" else flash_firmware
    fw(fb, op, backend, **cfg)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    sess = CoVerifySession(_firmware, congestion=CongestionConfig())
    sess.register_op("matmul", **matmul_backends(MM["tile"]))
    sess.register_op("flash", **flash_backends(FL["bq"], FL["bk"]))
    for b in ("oracle", "compiled"):
        sess.add_cell("matmul", b, MM)
        sess.add_cell("flash", b, FL)
    assert sess.run(max_workers=4).passed            # compiles
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    reports = []
    with TraceAnnotation("bench.window"):
        for _ in range(SWEEPS):
            with TraceAnnotation("bench.sweep"):
                reports.append(sess.run(max_workers=4))
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(out))
    return sp.load(path), reports, path


def test_each_thread_nests_as_the_span_table(traced):
    spans, reports, _ = traced
    assert all(r.passed for r in reports)
    kids = sp.children(spans)
    pairs = {(spans[i].name, spans[k].name)
             for i, ks in kids.items() for k in ks}
    assert pairs == TREE
    sweeps = sp.sweeps(spans)
    assert [s.args["sweep"] for s in sweeps] == [2, 3]
    assert all(s.args["cells"] == 4 for s in sweeps)
    caller = sweeps[0].thread
    for sweep in sweeps:
        cells = [s for s in sp.of_sweep(spans, sweep) if s.name == "fb.cell"]
        assert len(cells) == 4
        assert all(c.thread != caller for c in cells)
        assert {c.args["sweep"] for c in cells} == {sweep.args["sweep"]}


def _cell_spans(spans, sweep, label):
    (cell,) = [s for s in sp.of_sweep(spans, sweep)
               if s.name == "fb.cell" and s.args["cell"] == label]
    return cell, [s for s in spans if s.thread == cell.thread
                  and s.within((cell.start, cell.end))]


def test_counts_are_what_the_firmware_moved(traced):
    spans, reports, _ = traced
    mm_bursts = len(mm_ops.transactions(
        MM["size"], MM["size"], MM["size"], bm=MM["tile"], bn=MM["tile"],
        bk=MM["tile"], dtype_bytes=4))
    fl_bursts = len(fa_ops.transactions(
        FL["batch"], FL["heads"], FL["seq"], FL["seq"], FL["dim"],
        bq=FL["bq"], bk=FL["bk"], causal=True, dtype_bytes=4))
    for sweep, rep in zip(sp.sweeps(spans), reports):
        for r in rep.cells:
            _, own = _cell_spans(spans, sweep, r.cell.label)
            ins = {"matmul": ("a", "b"), "flash": ("q", "k", "v")}[r.cell.op]
            outs = [n for n in r.outputs if n not in ins]
            nb = {n: a.nbytes for n, a in r.outputs.items()}
            assert sp.arg_sum(own, "fb.mem.alloc", "bytes") == sum(nb.values())
            assert sp.arg_sum(own, "fb.mem.host_write", "bytes") == \
                sum(nb[n] for n in ins)
            assert sp.arg_sum(own, "fb.mem.dev_read", "bytes") == \
                sum(nb[n] for n in ins)
            assert sp.arg_sum(own, "fb.mem.dev_write", "bytes") == \
                sum(nb[n] for n in outs)
            assert sp.arg_sum(own, "fb.cell.collect", "bytes") == \
                sum(nb.values())
            assert sp.arg_sum(own, "fb.launch.bursts", "bursts") == \
                (mm_bursts if r.cell.op == "matmul" else fl_bursts)
            assert sp.arg_sum(own, "fb.link", "bursts") == \
                r.counters["totals"]["transactions"]
            (launch,) = [s for s in own if s.name == "fb.launch"]
            assert launch.args == {"op": r.cell.op, "backend": r.cell.backend}
        compares = [s for s in sp.of_sweep(spans, sweep)
                    if s.name == "fb.sweep.compare"]
        assert len(compares) == 2
        for s in compares:
            oracle = next(r for r in rep.cells if r.cell.backend == "oracle"
                          and s.args["group"].startswith(r.cell.op + "["))
            assert s.args["elems"] == sum(a.size
                                          for a in oracle.outputs.values())


def test_report_times_are_the_span_durations(traced):
    spans, reports, _ = traced
    ms = 1e-3
    for sweep, rep in zip(sp.sweeps(spans), reports):
        inside = sp.of_sweep(spans, sweep)

        def dur(name):
            return sum(s.dur for s in inside if s.name == name) * 1e-9
        assert set(rep.phase_seconds) == {"cells", "precheck", "compare",
                                          "bisect"}
        assert rep.wall_seconds == rep.phase_seconds["cells"]
        for phase in ("cells", "precheck", "compare", "bisect"):
            assert rep.phase_seconds[phase] == pytest.approx(
                dur(f"fb.sweep.{phase}"), abs=ms)
        for r in rep.cells:
            _, own = _cell_spans(spans, sweep, r.cell.label)
            (fw,) = [s for s in own if s.name == "fb.firmware"]
            assert r.seconds == pytest.approx(fw.dur * 1e-9, abs=ms)
        assert rep.summary()["phase_seconds"]["cells"] == round(
            rep.phase_seconds["cells"], 3)


def test_every_per_sweep_quantity_reads_a_number(traced):
    spans, _, _ = traced
    got = sp.per_sweep(spans)
    assert set(got) == {"compare_ms_per_sweep", "collect_ms_per_sweep",
                        "host_write_ms_per_sweep", "link_us_per_burst",
                        "cell_concurrency"}
    assert all(v is not None and v > 0 for v in got.values())
    assert got["cell_concurrency"] <= 4.0 + 1e-6


def test_span_tree_tool_prints_every_sweep(traced, capsys):
    _, _, path = traced
    tool = common.load_module(common.BENCH_DIR / "tools" / "span_tree.py")
    tool.main(path)
    out = capsys.readouterr().out
    assert out.count("\nsweep ") == SWEEPS
    assert "of bench.sweep" in out and "of the longest fb.cell" in out
    assert "cell_concurrency" in out


# ------------------------------------------------ hand-made span lists
S = sp.Span
A, B = ("host", 0), ("host", 1)


def _spans():
    return [S(tr.WINDOW_SPAN, 0, 1000, A), S("fb.sweep", 0, 900, A),
            S("fb.sweep.cells", 0, 600, A),
            S("fb.cell", 10, 500, B, {"sweep": 1}),
            S("fb.firmware", 10, 400, B), S("fb.link", 50, 150, B,
                                            {"bursts": 40}),
            S("fb.link", 200, 300, B, {"bursts": 60}),
            S("fb.cell.collect", 400, 480, B, {"bytes": 8}),
            S("fb.sweep.precheck", 600, 610, A),
            S("fb.sweep.compare", 610, 890, A, {"elems": 5})]


def test_self_time_less_children_on_the_same_thread():
    spans = _spans()
    own = dict(zip([s.name + str(s.start) for s in spans],
                   sp.self_ns(spans)))
    assert own["fb.firmware10"] == 390 - 200
    assert own["fb.cell10"] == 490 - 390 - 80
    # the pool thread's cell does not count against the caller's phase
    assert own["fb.sweep.cells0"] == 600
    assert own["fb.sweep0"] == 900 - 600 - 10 - 280


def test_cover_and_idle_while_open():
    spans = _spans()
    sweep = spans[1]
    phases = [s for s in spans if s.name in
              ("fb.sweep.cells", "fb.sweep.precheck", "fb.sweep.compare")]
    assert sp.cover(phases, sweep) == pytest.approx(890 / 900)
    cell = spans[3]
    assert sp.leaf_cover(spans, cell) == pytest.approx((100 + 100 + 80) / 490)
    assert sp.idle_while_open(spans, "fb.link", [(0, 100), (250, 1000)]) \
        == 50 + 50


def test_per_sweep_on_hand_made_spans():
    got = sp.per_sweep(_spans())
    assert got["compare_ms_per_sweep"] == pytest.approx(290e-6)
    assert got["collect_ms_per_sweep"] == pytest.approx(80e-6)
    assert got["host_write_ms_per_sweep"] is None
    assert got["link_us_per_burst"] == pytest.approx(200e-3 / 100)
    assert got["cell_concurrency"] == pytest.approx(490 / 600)


def _bench_run(extra_spans):
    mm = {"M": 256, "K": 384, "N": 512}
    fl = {"B": 1, "H": 4, "KH": 2, "S": 256, "D": 128}
    E = tr.Event
    ops = [E("systolic_matmul.1", 1_000, 31_000, "jit_systolic_matmul", "d0",
             "systolic_matmul.1 bf16[256,512]{1,0} custom-call(...)"),
           E("flash_attention_fwd.1", 40_000, 60_000,
             "jit_flash_attention_fwd", "d0",
             "(bf16[1,4,256,128]{3,2,1,0}, f32[1,4,256,1]) custom-call(...)"),
           E("fusion", 700_000, 900_000, "jit_dot_general", "d0", "fusion")]
    spans = [E(tr.WINDOW_SPAN, 0, 1_000_000),
             E("bench.sweep", 0, 990_000)] + extra_spans
    info = {"traced_sweeps": 1, "sweep_seconds": [0.9],
            "matmul": mm, "flash": fl, "itemsize": 2,
            "traced_spans": {"sweep": [0.9, 1], "launch": [0.5, 4],
                             "backend": [0.2, 4]}}
    return {"trace": tr.Trace(ops, spans, ["d0"]), "info": info,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


FB = [tr.Event("fb.sweep", 100, 980_000),
      tr.Event("fb.sweep.cells", 200, 650_000),
      tr.Event("fb.cell", 61_000, 640_000),
      tr.Event("fb.firmware", 61_000, 600_000),
      tr.Event("fb.mem.host_write", 62_000, 500_000),
      tr.Event("fb.sweep.compare", 910_000, 975_000)]


@pytest.mark.parametrize("metric", sorted(
    m["name"] for m in common.load_json(common.ROOT / "BENCHMARK.json")
    ["per_layer"]))
def test_accepted_readers_ignore_program_spans(metric):
    mod = common.load_module(common.BENCH_DIR / "metrics" / f"{metric}.py")
    assert mod.read(_bench_run(list(FB))) == mod.read(_bench_run([]))


def test_gaps_are_named_by_the_program_leaf():
    """With the program's spans beside the benchmark's, the rule that
    names a gap by the innermost open span names the program's leaf."""
    gaps = _bench_run(list(FB))["trace"].breakdown()["idle_gaps"]
    assert [g[0] for g in gaps] == ["fb.mem.host_write", "fb.sweep.compare",
                                    "fb.sweep.cells", "fb.sweep.cells"]
    assert [g[1] for g in gaps] == pytest.approx([640e-6, 100e-6, 9e-6,
                                                  1e-6])
    plain = _bench_run([])["trace"].breakdown()["idle_gaps"]
    assert [g[0] for g in plain] == ["bench.sweep"] * 4

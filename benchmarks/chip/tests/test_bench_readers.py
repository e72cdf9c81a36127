"""Every per-layer metric's reader, on a hand-made trace of the cell it
lists: each finds its events and returns a number, and finds nothing (and
returns None) on a trace without them."""
import pytest

from bench import common, trace as tr

E = tr.Event
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
BENCH = common.load_json(common.ROOT / "BENCHMARK.json")


def _coverify_run():
    mm = {"M": 256, "K": 384, "N": 512}
    fl = {"B": 1, "H": 4, "KH": 2, "S": 256, "D": 128}
    ops = [E("custom-call.1", 1_000, 31_000, "jit__lambda", "d0",
             "custom-call.1 %x = bf16[256,512]{1,0} custom-call(...)"),
           E("custom-call.2", 40_000, 60_000, "jit__lambda", "d0",
             "custom-call.2 (bf16[1,4,256,128]{3,2,1,0}, f32[1,4,256,1]) "
             "custom-call(...)"),
           E("fusion", 70_000, 90_000, "jit_dot_general", "d0", "fusion")]
    spans = [E(tr.WINDOW_SPAN, 0, 1_000_000)]
    info = {"traced_sweeps": 1, "sweep_seconds": [0.9, 0.8, 4.0],
            "matmul": mm, "flash": fl, "itemsize": 2,
            "traced_spans": {"sweep": [0.9, 1], "launch": [0.5, 4],
                             "backend": [0.2, 4]}}
    return {"trace": tr.Trace(ops, spans, ["d0"]), "info": info,
            "peak": PEAK}


READERS = sorted(p.stem for p in (common.BENCH_DIR / "metrics").glob("*.py"))


def test_every_listed_metric_has_a_reader():
    assert {m["name"] for m in BENCH["per_layer"]} <= set(READERS)


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_its_cells(metric):
    run = _coverify_run()
    mod = common.load_module(common.BENCH_DIR / "metrics" / f"{metric}.py")
    v = mod.read(run)
    assert v is not None and v > 0
    if any(w in metric for w in ("share", "roofline", "mfu")):
        assert v <= 100.0
    empty = dict(run, trace=tr.Trace([], [E(tr.WINDOW_SPAN, 0, 10)], []),
                 info=dict(run["info"], traced_spans={}, traced_sweeps=0,
                           sweep_seconds=[]))
    assert mod.read(empty) is None


def test_sweep_median_ignores_a_stalled_sweep():
    mod = common.load_module(common.BENCH_DIR / "metrics" /
                             "sweep_median_ms.py")
    assert mod.read(_coverify_run()) == pytest.approx(900.0)

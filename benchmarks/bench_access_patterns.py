"""Fig. 9 reproduction: memory-access-pattern heatmaps (address x time) for
a small CNN and ResNet-18 through the bridge.  The ping-pong activation
buffering of the firmware is visible as alternating address bands in the
input-read heatmap, and the weights stream as a monotonically advancing
band — the two signatures the paper calls out.
"""
from __future__ import annotations

from pathlib import Path

from benchmarks.cnn_driver import (gops, resnet18_specs, run_cnn,
                                   small_cnn_specs)

ART = Path(__file__).resolve().parent / "artifacts"


def run() -> list[str]:
    rows = ["case,model,gop,reads,writes,heatmap_file"]
    for name, specs in (("small_cnn", small_cnn_specs(16)),
                        ("resnet18", resnet18_specs(36))):
        fb = run_cnn(specs, backend="oracle")
        reads = sum(1 for t in fb.log.txs if t.kind == "read")
        writes = sum(1 for t in fb.log.txs if t.kind == "write")
        out = ART / f"fig9_heatmap_{name}.txt"
        out.parent.mkdir(parents=True, exist_ok=True)
        txt = ["# address (vertical, high->low) x time (horizontal)",
               "## reads", fb.log.render_heatmap(24, 72, kind="read"),
               "## writes", fb.log.render_heatmap(24, 72, kind="write")]
        out.write_text("\n".join(txt))
        rows.append(f"fig9,{name},{gops(specs):.3f},{reads},{writes},"
                    f"{out.name}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("\n".join(run()))

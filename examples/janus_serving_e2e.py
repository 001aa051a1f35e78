"""End-to-end driver (the paper's kind = SERVING): batched frame requests
through the full Janus stack under three network scenarios, with real split
model math on a reduced ViT (`--smoke-model`, runs on the CPU) and the
paper-calibrated timing plane.

    PYTHONPATH=src python examples/janus_serving_e2e.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.launch import serve

for net, mob in (("4g", "driving"), ("5g", "walking"), ("wifi", "static")):
    print(f"\n=== {net}/{mob} ===")
    serve.main(["--network", net, "--mobility", mob, "--frames", "40",
                "--sla-ms", "300", "--execute", "--smoke-model"])

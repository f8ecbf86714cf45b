"""A checkout of the benchmark alone, with small configurations added, for
the tests on the CPU."""
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def make_root(tmp: Path) -> Path:
    """A checkout of the benchmark alone (BENCHMARK.json and bench/, its
    tests left out) with small configurations and cells added the way a
    later change adds them: new files and new BENCHMARK.json entries, no
    existing file edited. ``tiny`` runs on one device; ``tiny4``, whose
    heads divide by four, on four; ``small`` is wide enough for the int8
    control to show. Returns the new root."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    data = BENCH / "tests" / "data"
    for name in ("tiny", "tiny4", "small"):
        shutil.copy(data / f"{name}.json",
                    root / "bench" / "configs" / f"{name}.json")
    for t in ("tiny_chat", "tiny_docs", "small_chat"):
        shutil.copy(data / f"{t}.json", root / "bench" / "traffic" / f"{t}.json")
    for cellf, name in (("tiny_chat", "tiny.chat"), ("tiny_docs", "tiny.docs"),
                        ("tiny_docs", "tiny4.docs"),
                        ("small_chat", "small.chat")):
        shutil.copy(data / f"{cellf}.cell.json",
                    root / "bench" / "cells" / f"{name}.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("tiny", "tiny4", "small"):
        bench["configs"].append({"name": name, "source": "tests",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "tests"})
    chat = [f"{name}.chat" for name in ("tiny", "small")]
    docs = [f"{name}.docs" for name in ("tiny", "tiny4")]
    for name, chips, traffic in (("tiny.chat", 1, "tiny_chat"),
                                 ("small.chat", 1, "small_chat"),
                                 ("tiny.docs", 1, "tiny_docs"),
                                 ("tiny4.docs", 4, "tiny_docs")):
        bench["workloads"].append({"name": name, "config": name.split(".")[0],
                                   "traffic": traffic, "chips": chips,
                                   "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "granite-8b-1chip.chat" in m.get("workloads", []):
            m["workloads"] += chat
    # the offline metrics a documents cell reports, added as a later
    # change adds them
    bench["end_to_end"].insert(0, {
        "name": "output_tok_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": docs})
    for name, unit in (("prefill_ms_per_ktok.offline", "ms"),
                       ("decode_step_ms.offline", "ms"),
                       ("compiles_in_window.offline", "programs")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "host_clock", "layer": "tests",
            "moves": "output_tok_s", "workloads": docs})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def set_limits(root: Path, workload: str, limits) -> None:
    """Compare other numbers in a cell of the checkout at ``root``."""
    path = root / "bench" / "cells" / f"{workload}.json"
    cellf = json.loads(path.read_text())
    cellf["limits"] = dict(limits)
    path.write_text(json.dumps(cellf, indent=1))

#!/usr/bin/env python3
"""Both readings a cell's limits lie between, on the chip at the cell's size.

    python3 benchmark/control.py --workload <cell>

Builds the cell's extractor as ``run.py`` does, takes the timed path's
features of the check input, then ``references/<config>.py features()``
(the lower reading) and ``control()`` (the same in the nearest lower
precision, in the program's place: the upper reading), and prints what
``checks/<config>.py compare()`` makes of each as one JSON line. ``run.py``
never calls ``control``; ``PERF.md`` quotes this file's output where it sets
a limit. Exits non-zero without a chip, or where the control passes.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

from vftbench import corpus, device, manifest, program  # noqa: E402


def main(argv=None, root: Path = manifest.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    opts = ap.parse_args(argv)
    cell = manifest.Cell(manifest.load_manifest(root), opts.workload, root)
    program.place_compile_cache(root)
    try:
        device.require_chip(cell.chips, cell.bench / "peaks.json")
    except device.NoChip as e:
        print(f"vftbench: cannot measure {cell.name}: {e}", file=sys.stderr)
        return 3
    import jax
    out_dir = Path(root) / "benchmark_out" / cell.name
    block = cell.traffic.get("check_video") or cell.corpus_spec()
    (check,) = corpus.build_fixed(
        out_dir.parent, block,
        [corpus.frames_for(int(cell.config["check_units"]),
                           cell.config["unit"])],
        cell.corpus_kind(block)).values()
    extractor = program.build_extractor(
        program.program_args(cell.config, out_dir / "control"))
    key = str(extractor.feature_type)
    ran = extractor.extract(check)
    params = extractor.runner.params
    del extractor
    compare = cell.config_function("checks", "compare")
    readings = {}
    with jax.default_matmul_precision("highest"):
        reference = cell.config_function("references", "features")(
            params, cell.config, check)
        readings["program"] = compare(ran, reference, key)
        readings["control"] = compare(
            cell.config_function("references", "control")(
                params, cell.config, check), reference, key)
    print(json.dumps(readings))
    return 0 if readings["program"]["ok"] and not readings["control"]["ok"] \
        else 1


if __name__ == "__main__":
    sys.exit(main())

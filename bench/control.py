"""The control of a cell's comparison: the plain reference put in the
program's place, its estimator computed in bfloat16, one precision below the
float32 the configurations state.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

For each seed it answers the run's first job (the same graph and sample
vector) with the control and holds that answer against the float32
reference exactly as a run holds the program's. Prints one line per seed
and, last, a JSON object with every reading and the cell's limits. The
benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare, graphgen, harness  # noqa: E402


def control_answer(ref_mod, edges, config: dict, x, devices) -> dict:
    """The control's answer to one job, in the program's answer format."""
    import jax.numpy as jnp

    out = ref_mod.run(edges, config, x, devices, dtype=jnp.bfloat16)
    return {"seeds": out["seeds"], "gains": out["est_at_seed"],
            "scores": out["scores"], "rebuilds": out["rebuilds"],
            "build_sweeps": out["build_sweeps"]}


def readings(cell: harness.Cell, seeds, devices) -> list:
    ref_mod = harness.reference(cell.config["reference"])
    edges = graphgen.for_config(cell.config)
    registers = int(cell.config["spec"]["num_registers"])
    out = []
    for seed in seeds:
        x = harness.draw_x(seed, 1, registers)
        answer = control_answer(ref_mod, edges, cell.config, x, devices)
        ref = ref_mod.run(edges, cell.config, x, devices,
                          seeds=answer["seeds"])
        out.append({"seed": seed, **compare.compare(answer, ref, edges.n)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_chips(cell.chips)[:cell.chips]
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    rows = readings(cell, [int(s) for s in args.seeds.split(",")], devices)
    limits = cell.config["limits"]
    for row in rows:
        failed = [k for k in compare.NAMES if row[k] > limits[k]]
        print(f"control seed {row['seed']}: " + " ".join(
            f"{k}={row[k]!r}" for k in compare.NAMES)
            + f" -> fails {failed or 'nothing'}", flush=True)
    print(json.dumps({"workload": cell.name, "readings": rows,
                      "limits": limits,
                      "seconds": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Where a cell's limits come from, and the proof that they hold: the
readings of `correct`'s numbers on the chip, at the cell's own size, over
many seeds and in one process, each judged by `limits/<cell>.json` as a run
of the cell is.

    python3 perfbench/probe.py --workload <cell> --seeds 12 --control-seeds 3 --out <file.jsonl>
    python3 perfbench/probe.py --workload <cell> --seeds 0 --control-seeds 8 --out <file.jsonl>

For each of ``--seeds``: the program's first steps against the plain
reference (the lower readings; `correct` has to read true). For each of
``--control-seeds``: the control, which is the reference in the next
precision down put in the program's place (the upper readings), and the
reference with each fault planted; `correct` has to read false for every one
of them, or the command exits 1. With ``--seeds 0`` the program is not built
and one chip is enough, whatever the cell asks for: the references run on
one. One JSON line per reading, with ``correct`` and the numbers that failed.
The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def probe(cell, seeds: int, control_seeds: int, first_seed: int,
          faults: list[str], write, require_chip: bool = True) -> bool:
    """``write(line)`` once per reading; whether every reading was judged as
    it has to be."""
    from perfbench import compare, harness

    config, traffic = cell.config, cell.traffic
    devices = harness.find_devices(cell.chips if seeds else 1, require_chip)
    if require_chip:
        harness.enable_compile_cache()
    module = cell.reference_module()
    follow = getattr(module, traffic["reference"])
    n_dispatches = traffic["follow_dispatches"]
    program = cell.entry_module().build(
        config, traffic,
        lambda: module.make_inputs(
            config, traffic, harness.key_from_seed(first_seed)),
        devices) if seeds else None
    n_steps = n_dispatches * (
        program.rounds_per_dispatch if program is not None
        else traffic.get("rounds_per_dispatch", 1))
    as_expected = True

    def emit(seed, what, found, reference, want):
        nonlocal as_expected
        numbers = compare.numbers(found, reference)
        gaps = compare.leaf_gaps(found["grad_norms"], reference["grad_norms"])
        correct, rows = compare.judge(numbers, cell.limits)
        as_expected &= correct == want
        write({"workload": cell.name, "seed": seed, "what": what,
               "correct": correct, "expected": want,
               "over_its_limit": sorted(
                   k for k, r in rows.items() if r["limit"] is not None
                   and not r["value"] <= r["limit"]),
               "grad_norm_at": max(gaps, key=gaps.get),
               "grad_gap_deciles": [round(q, 6) for q in statistics.quantiles(
                   gaps.values(), n=10)] if len(gaps) > 1 else [],
               **numbers})

    for i in range(max(seeds, control_seeds)):
        seed = first_seed + 7919 * i
        key = harness.key_from_seed(seed)

        def make_inputs():
            return module.make_inputs(config, traffic, key)

        observed = None
        if i < seeds:
            program.restart(make_inputs)
            observed = program.first_steps(n_dispatches)
            program.drop_state()  # free the chip for the references
            gc.collect()
        reference = follow(config, traffic, make_inputs(), n_steps)
        if observed is not None:
            emit(seed, "program", observed, reference, True)
        if i < control_seeds:
            control = follow(config, traffic, make_inputs(), n_steps,
                             precision=traffic["control_precision"])
            emit(seed, "control:" + traffic["control_precision"], control,
                 reference, False)
            for fault in faults:
                broken = follow(config, traffic, make_inputs(), n_steps,
                                fault=fault)
                emit(seed, "fault:" + fault, broken, reference, False)
    return as_expected


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=2**31 + 1000)
    parser.add_argument("--faults", default="half_batch,no_exchange")
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)
    from perfbench import cells

    with open(args.out, "a") as file:
        def write(line):
            print(json.dumps(line), file=file, flush=True)
            print(json.dumps(line), flush=True)

        as_expected = probe(
            cells.load_cell(args.workload), args.seeds, args.control_seeds,
            args.first_seed, [f for f in args.faults.split(",") if f], write)
    print(f"every reading judged as expected: {as_expected}", file=sys.stderr)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())

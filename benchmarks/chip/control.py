"""The readings the correctness limits are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 ...
        [--control-seeds 1 2 3]

One process; per seed it makes that seed's weights, serves one burst at the
cell's own sizes through the cell's entry, samples the served sequences as a
run does, and prints one JSON line: ``program``, the widest logit gap of the
served tokens (the lower reading), and, for the control seeds, ``control``,
the widest gap of the tokens that the float8 control of the reference ranks
first (the upper reading).  Exits non-zero without a TPU.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import check, harness, spec, traffic, weights  # noqa: E402


def readings(cell, seeds, control_seeds, require_chip=True, cfg=None):
    harness._devices(cell, require_chip)
    if require_chip:
        harness.use_compile_cache()
    cfg = cfg or spec.program_config(cell.config)
    mix = cell.mix
    entry_mod = spec.entry_module(mix["entry"])
    for seed in seeds:
        t0 = time.perf_counter()
        params = weights.make_params(cfg, cell.config["initializer_range"], seed,
                                     cell.family.init_kind)
        entry = entry_mod.Entry(params, cfg, dict(mix))
        burst = traffic.Generator(mix, cfg.vocab, seed).burst(0)
        res = entry.call(burst.wires)
        served = []
        bad = check.check_wires(burst, res.responses, mix["max_new"], cfg.vocab, served)
        entry.close()
        del entry
        gc.collect()
        seqs = check.sample(served, mix["check_sequences"], seed)
        out = {"seed": seed, "bad_wires": bad, "sequences": len(seqs),
               "program": check.logit_gaps(cell, params, seqs)}
        if seed in control_seeds:
            out["control"] = check.logit_gaps(cell, params, seqs, control=True)
        out["seconds"] = time.perf_counter() - t0
        del params
        gc.collect()
        yield out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    try:
        for line in readings(cell, args.seeds, set(args.control_seeds)):
            print(json.dumps(line), flush=True)
    except harness.NoChip as exc:
        print(f"[control] {exc}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())

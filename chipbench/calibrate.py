"""Readings from which a cell's correctness limits are set.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --fault-seeds 1,2,3 --out <file.jsonl>

In one process, for each seed of ``--seeds``: one run of the cell as the
benchmark makes it (a one-cycle window), giving the program's readings
against the float32 reference: the lower readings.  For each seed of
``--control-seeds``: the reference computed with float8 weight products
(the control) against the float32 reference.  For each seed of
``--fault-seeds`` and each planted fault of ``reference/train.py``: the
faulty reference against the float32 reference.  A step that returns its
state unchanged reads 1 on ``change`` by definition and needs no run.
Every reading is one JSON line.  Needs the cell's chips, like ``run.py``.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, data, run, spec  # noqa: E402


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from chipbench.program import Program, import_program
    from chipbench.reference import train as reference

    cell = spec.load_cell(args.workload)
    import_program(ROOT)
    devices = run.accelerator(cell.chips)
    run.enable_cache()
    conf, traf = cell.config, cell.traffic
    prog = Program(conf, traf, devices)
    n_check = run.check_steps(traf["sync"])
    refs = {}
    out = open(args.out, "a")

    def emit(rec):
        out.write(json.dumps(rec) + "\n")
        out.flush()
        print(json.dumps(rec), flush=True)

    for s in args.seeds:
        res = run.run_cell(args.workload, s, 0.0, False, devices,
                           program=prog, keep=True)
        refs[s] = res["records"]["reference"]
        emit({"seed": s, "kind": "program", "readings": res["readings"],
              "where": res["where"], "correct": res["correct"],
              "losses": res["records"]["program"]["losses"].tolist(),
              "ref_losses": refs[s]["losses"].tolist()})
        del res
        gc.collect()
    variants = [(s, "control", "fp8", None) for s in args.control_seeds] + [
        (s, f, "fp32", f) for s in args.fault_seeds
        for f in reference.faults_for(traf["sync"])]
    for s, kind, precision, fault in variants:
        pool = data.batch_pool(s, int(traf["pool"]), prog.pods, prog.rows,
                               prog.seq, conf["model"]["vocab_size"])
        args_ = (conf["model"], conf["reference"], traf["sync"],
                 float(conf["lr"]), run.key_for(s), pool[:n_check])
        if s not in refs:
            refs[s] = reference.train(*args_, devices=devices)
        other = reference.train(*args_, precision=precision, fault=fault,
                                devices=devices)
        numbers = check.compare(other, refs[s])
        emit({"seed": s, "kind": kind, "readings": {
            k: numbers[k] for k in check.NUMBERS}, "where": numbers["where"],
            "losses": other["losses"].tolist()})
    out.close()


if __name__ == "__main__":
    main()

"""``hbm_roofline_pct``: the traced window's least HBM bytes, from the
bytes model ``perfbench/bytes/<ksp>-<pc>.py`` (per iteration times the
window's iterations, plus per solve times its solves), over the device
busy time from the trace times the HBM peak of every chip used
(``perfbench/peaks.json``). It reads the kernels (ops/pallas_stencil.py
and the XLA fusions) against the memory roofline. Moves solve_s."""

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def device_peaks(kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def read(run):
    import importlib.util
    red = run.trace
    if not red or red["busy_s"] <= 0:
        return None
    t = run.traffic
    path = os.path.join(HERE, "bytes", f"{t['ksp_type']}-{t['pc_type']}.py")
    spec = importlib.util.spec_from_file_location("perfbench_bytes", path)
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    peaks = device_peaks(run.device_kind)
    vmem = peaks["vmem_bytes"]
    iters = sum(s["iterations"] for s in run.solves)
    need = (iters * model.per_iteration(run.info, vmem)
            + len(run.solves) * model.per_solve(run.info, vmem))
    if need <= 0:
        return None
    rate = need / (red["busy_s"] * run.chips)
    return 100.0 * rate / peaks["hbm_bytes_per_s"]

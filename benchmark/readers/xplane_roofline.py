"""Device trace: the least time the chip could take for the work the
matched kernels had to do, over their traced time. The work comes from
the runner's values (computed by ``flops.py`` from shapes):
``flops``/``bytes`` name values for the whole traced window, or, with
``per_step`` set, values per step that are multiplied by the number of
steps the traced window held (``traced_steps``)."""

import flops as flops_lib


def read(params, run):
    tr, v = run.trace, run.values
    if tr is None:
        return None
    t = tr.seconds_matching(params["patterns"], params.get("opcode"))
    if t <= 0:
        return None
    mult = v["traced_steps"] if params.get("per_step") else 1.0
    need_f = v.get(params.get("flops", ""), 0.0) * mult
    need_b = v.get(params.get("bytes", ""), 0.0) * mult
    if need_f <= 0 and need_b <= 0:
        return None
    least = flops_lib.roofline_seconds(need_f, need_b, {
        "bf16_flops_per_s": v["peak_bf16_flops_per_s"],
        "hbm_bytes_per_s": v["peak_hbm_bytes_per_s"]})
    run.notes.append(f"{params.get('label', 'roofline')}: {least['bound']}"
                     f"-bound, least {least['seconds']:.6f}s of {t:.6f}s traced")
    return 100.0 * least["seconds"] / t

"""The fused runtime's ``device-converge`` phase per decomposition
(``KCoreResult.phase_s``): host staging (blocked layout, host-to-device
copies), any compile, and the device while_loop, mean over the window's
decompositions (s)."""


def read(run):
    vals = [s["phase_s"]["device-converge"] for s in run.steps
            if "device-converge" in s.get("phase_s", {})]
    return sum(vals) / len(vals) if vals else None

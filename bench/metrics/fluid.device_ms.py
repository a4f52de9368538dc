"""fluid.device_ms: device milliseconds of the fluid scan programs
(``jit_fluid_run``) per request, over the traced requests."""


def read(rec):
    tr = rec["trace"]
    if not tr or not rec["traced_requests"]:
        return None
    ms = 1e3 * sum(v for k, v in tr["programs"].items()
                   if k.startswith("jit_fluid_run"))
    return ms / rec["traced_requests"] if ms > 0 else None

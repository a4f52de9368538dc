"""hybrid.fct_err_pct: mean per-flow FCT error against the packet
reference, in percent (the worst request of the window)."""


def read(rec):
    return 100 * rec["compared"]["fct_err_mean"]

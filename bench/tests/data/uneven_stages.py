"""A stage rule for the tests: stages of different layer counts, dense
and MoE layers priced per layer from the configuration, and expert
gradients synced over the expert-DP groups."""
from bench.reference.deployment import StageLoad


def stage_loads(cfg: dict) -> list[StageLoad]:
    unit = cfg["per_layer"]
    loads = []
    for stage in cfg["stages"]:
        layers = stage["dense_layers"] + stage["moe_layers"]
        t_fwd = layers * unit["t_fwd"]
        loads.append(StageLoad(
            t_fwd=t_fwd, t_bwd=2 * t_fwd,
            a2a_bytes=stage["moe_layers"] * unit["a2a_bytes"],
            act_bytes=cfg["act_bytes"],
            dense_grad_bytes=layers * unit["dense_grad_bytes"],
            expert_grad_bytes=stage["moe_layers"] * unit["expert_grad_bytes"]))
    return loads

"""Model FLOPs utilization of the whole window: the FLOPs training needs
(each step's ``model_flops`` from the model module, from true node and
edge counts) over the window's seconds, as a share of the chip's bf16
peak."""


def read(run: dict) -> float | None:
    if run["peaks"] is None or run["window_s"] <= 0:
        return None
    flops = sum(r["model_flops"] for r in run["steps"])
    return 100.0 * flops / run["window_s"] / run["peaks"]["flops"]

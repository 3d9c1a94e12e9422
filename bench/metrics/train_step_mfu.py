"""The whole train step's share of the chips' bf16 peak: the operations a
step requires (``counts.train_step_flops``) times steps per second of the
window, over chips times peak."""


def read(run):
    if not run["steps"]:
        return None
    flops = run["counts"].train_step_flops(run["config"], run["batch"], run["n_gaussians"])
    rate = flops * run["steps"] / run["window_s"]
    return 100.0 * rate / (run["chips"] * run["peak"]["flops_bf16_per_s"])

"""Readers of the device trace.  ``read(metric, run)`` gets the metric's
data file and what the run holds (``trace``: the reduction of
``xplane.reduce_trace`` or None; ``facts``: what the driver counted;
``peaks``; ``model``; ``counts``: the family's count functions) and
returns the number, or None where there is nothing to read."""
import common
import xplane


def _window_calls(run):
    f = run["facts"]
    return [c for c in run["trace"].get("calls", ())
            if f["w0"] <= c["t"] < f["w1"]]


def idle_pct(metric, run):
    t = run["trace"]
    if not t or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def train_mfu_pct(metric, run):
    """The model's FLOPs of every execution of the step program that the
    trace holds, over the traced window times the chip's peak."""
    t = run["trace"]
    if not t or not run["peaks"]:
        return None
    steps = len(t["programs"].get(metric["per_program"], ()))
    if not steps:
        return None
    flops = run["counts"].model_flops_per_step(run["model"]) * steps
    return 100.0 * flops / (t["window_s"] * run["peaks"]["bf16_flops_per_s"])


def serve_mfu_pct(metric, run):
    t = run["trace"]
    if not t or not run["peaks"]:
        return None
    calls = _window_calls(run)
    if not calls:
        return None
    flops = run["counts"].step_flops(run["model"], calls)
    return 100.0 * flops / (t["window_s"] * run["peaks"]["bf16_flops_per_s"])


def roofline_pct(metric, run):
    """The least time the chip could take for the work the matched ops
    had to do (``need``: a count function's name; ``bound``: which peak
    bounds it) over the device time of those ops."""
    t = run["trace"]
    if not t or not run["peaks"]:
        return None
    seconds, n = xplane.op_seconds(t, metric["match"])
    if not n or seconds <= 0:
        return None
    need = metric["need"]
    counts, model = run["counts"], run["model"]
    if need == "train_step_flops":
        work = counts.train_step_flops(model) * len(
            t["programs"].get(metric["per_program"], ()))
    elif need == "decode_kv_read_bytes":
        calls = [c for c in _window_calls(run) if c["kind"] == "decode"]
        work = counts.decode_kv_read_bytes(
            model, sum(c["context"] for c in calls))
    elif need == "prefill_attention_flops":
        calls = [c for c in _window_calls(run)
                 if c["kind"] == "prefill" and not c["start"]]
        work = sum(counts.prefill_attention_flops(model, c["true"])
                   for c in calls)
    else:
        raise ValueError("no count %r" % need)
    if not work:
        return None
    return 100.0 * work / run["peaks"][metric["bound"]] / seconds


def program_median_ms(metric, run):
    t = run["trace"]
    if not t:
        return None
    runs = t["programs"].get(metric["program"])
    return 1e3 * common.median(runs) if runs else None

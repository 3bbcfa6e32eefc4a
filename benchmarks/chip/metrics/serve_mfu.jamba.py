"""Model FLOP/s utilisation of Jamba serving, in percent: the operations of
every prompt and generated token of the requests completed in the window
(counts_jamba.py; an expert layer counts K x H / E held experts a token,
top-2 x 8/16 = one) over the window's seconds times the chips times the
chip's bf16 peak."""
import harness

counts_jamba = harness.sibling("counts_jamba")
tokens = harness.load_module(harness.HERE / "metrics" / "serve_tokens_per_s.py",
                             "chip_metric")


def read(view):
    if view.trace is None:
        return None
    r, c = view.records, view.cell.config
    flops = sum(counts_jamba.request_flops(c, len(q["prompt"]), q["out_len"])
                for q in tokens.completed(r))
    return 100.0 * flops / (r["window_s"] * r["chips"]
                            * view.peaks["bf16_flops_per_s"])

"""step.mfu: model FLOPs of the tokens prefilled and decoded for requests
in the traced steps (2 per weight multiplied, plus causal attention), over
the traced window times the chip's bf16 peak, in percent."""


def read(facts):
    tr = facts["trace"]
    if not tr or tr["model_flops"] <= 0:
        return None
    return 100.0 * tr["model_flops"] / (tr["window_s"]
                                        * tr["peaks"]["bf16_flops"])

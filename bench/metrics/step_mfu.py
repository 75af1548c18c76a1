"""The whole serving step's share of the chip's bf16 peak: 2 x parameters
x tokens the model processed in the traced window (prompt tokens
prefilled and output tokens decoded) over the traced window times the
peak (whole step, device).  The parameters are those one token's forward
pass multiplies by, as the configuration's builder counts them."""


def read(rec):
    tr, host = rec["trace"], rec["host"]
    if not tr or not host:
        return None
    tokens = host["prompt_tokens"] + host["output_tokens"]
    if tokens <= 0 or tr["window_s"] <= 0:
        return None
    flops = 2.0 * rec["params"] * tokens
    return 100.0 * flops / (tr["window_s"] * rec["peaks"]["peak_flops_bf16"])

"""The whole step's share of the chip's peak in the traced slice: the
operations the model needs for the tokens the slice processed (counted by
the configuration's family from its shapes; recompute not counted) over
the slice's seconds and chips x peak FLOP/s."""


def read(params, rec, ctx):
    sl = rec.get("slice")
    if not sl or ctx.peaks is None or not sl["model_flops"]:
        return None
    return 100.0 * sl["model_flops"] / sl["seconds"] / (
        int(ctx.cell["chips"]) * ctx.peaks["bf16_flops_per_s"])

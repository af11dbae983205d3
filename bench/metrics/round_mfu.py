"""round_mfu (%): the FLOPs of a round's local training in each
client's own architecture (forward + backward, union padding not
counted) over the mean round time of the untraced part of the traced
run times the chip's bf16 peak (``roofline.PEAKS``)."""


def read(ctx):
    plain = ctx.get("plain")
    if not plain or not plain["rounds"]:
        return None
    round_s = plain["seconds"] / plain["rounds"]
    chips = ctx["device"]["count"]
    return 100.0 * ctx["model_flops_per_round"] / (
        round_s * chips * ctx["peaks"]["bf16_flops"])

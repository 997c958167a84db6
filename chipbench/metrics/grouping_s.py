"""Host seconds of Phase-1 grouping (``P4Trainer.form_groups``) in set-up;
the call ends in a device sync when its distances reach the host."""


def read(ctx):
    return ctx.setup.get("grouping_s")

"""Seconds of the program's world build (the contact plan), on the host
clock around the call in set-up."""


def read(ctx):
    return ctx.sim.world_build_s

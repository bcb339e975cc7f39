"""Set-up: seconds to make the graph on the device from the seed and
read back the vertex sets traffic draws from (host clock)."""


def read(ctx):
    return ctx.setup.get("graph_build_s")

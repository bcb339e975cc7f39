"""Set-up: seconds JAX spent compiling or reading the persistent
compile cache during set-up (its compile-duration events)."""


def read(ctx):
    return ctx.setup.get("compile_s")

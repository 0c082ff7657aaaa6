"""From ``run.py``'s first line to the window's start: imports, the program's
start-up, the seed's weights and shards, and the first units (which compile
or load the cell's programs)."""


def read(ctx):
    return ctx.setup["setup_s"]

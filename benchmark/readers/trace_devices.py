"""How many device planes of the trace ran anything inside the traced
window (``trace_reduce.clip_to_window``): the chips the cell's programs
were spread over. One for a program that places every run on the first
chip, the mesh's size where every request is a mesh program. (A CPU
rehearsal has one stand-in plane.) No arguments."""


def read(args: dict, ctx: dict):
    trace = ctx.get("trace")
    return None if trace is None else trace["devices"]

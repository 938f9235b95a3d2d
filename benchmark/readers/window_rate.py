"""Operations answered per second of the window: the operations all
clients had answered, over the time from the go signal until the last
client returned (each closed-loop client finishes the operation it has in
flight when the window's seconds are up, so that time is a little over
them). All the work and all the time count: an operation that failed or
timed out adds its time and no operation. (A failed operation or a wrong
answer also makes the run not correct, whatever its rate.)
``streams`` is [[operations answered, seconds until it returned]] for
each client."""


def read(args: dict, ctx: dict):
    streams = ctx["client"]["streams"]
    longest = max((t for _n, t in streams), default=0.0)
    return sum(n for n, _t in streams) / longest if longest > 0 else None

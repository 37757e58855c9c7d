"""Device time of the collectives (the clients' exchange: the allreduce's
``psum``s and ``pmax`` across chips) a round, on the fullest chip."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["rounds"] or not tr["collective_s"]:
        return None
    return 1e3 * tr["collective_s"] / tr["rounds"]

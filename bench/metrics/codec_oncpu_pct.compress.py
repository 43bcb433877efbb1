"""On-CPU seconds over wall seconds of the compress host stages that never
wait on the device, summed over their threads: below 100%, the threads
waited for the GIL, a lock or a core."""

STAGES = ("entropy_encode", "gae_verify")


def read(ctx):
    stats = [ctx.stages.get(name) for name in STAGES]
    if any(st is None or not hasattr(st, "cpu_seconds") for st in stats):
        return None
    wall = sum(st.seconds for st in stats)
    if wall <= 0:
        return None
    return 100.0 * sum(st.cpu_seconds for st in stats) / wall

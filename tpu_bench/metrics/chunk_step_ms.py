"""Host wall time of each ``JaxFleetStepper.step`` (which ends in host
arrays fed to the nodes), mean over the window's chunks. Moves
``tenant_s_per_s``."""


def read(ctx):
    walls = ctx.out.get("walls", {}).get("step", [])
    return 1e3 * sum(walls) / len(walls) if walls else None

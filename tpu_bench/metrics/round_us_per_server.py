"""The DYVERSE controller's overhead, the paper's own metric: the wall
time of each node's ``run_controller_round`` over the tenants (servers)
it hosts, in microseconds, mean over the window's rounds. Moves
``tenant_s_per_s``."""


def read(ctx):
    walls = ctx.out.get("walls", {}).get("round", [])
    return 1e6 * sum(walls) / len(walls) if walls else None

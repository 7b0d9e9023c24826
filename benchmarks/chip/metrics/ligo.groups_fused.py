"""ligo.groups_fused: leaf groups of the growth plan that run on the fused
LiGO kernels (under ``shard_map`` on a mesh), per executor build: the
program's counters ``ligo.groups_fused`` and ``ligo.groups_einsum``, which
count every group of each build once, on its route, read after the first
hop (which builds every executor the hops use) and scaled to the plan's
group count. None where the program keeps no such counters."""


def read(run):
    g = run.records.get("ligo_groups")
    if not g or not g["fused"] + g["einsum"]:
        return None
    return g["groups"] * g["fused"] / (g["fused"] + g["einsum"])

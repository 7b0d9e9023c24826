# Launch layer: mesh construction, dry-run, train/serve drivers.
# NOTE: repro.launch.dryrun sets XLA_FLAGS at import — import it only in a
# dedicated process (python -m repro.launch.dryrun).
from repro.launch.mesh import (PEAKS, make_host_mesh, make_mesh,
                               make_production_mesh, peaks)

__all__ = ["make_production_mesh", "make_mesh", "make_host_mesh",
           "PEAKS", "peaks"]

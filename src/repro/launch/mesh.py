"""Multi-host start-up and the 2-D ``(data, cand)`` mining mesh.

NOTE: importing this module never touches jax device state; the mesh is built
only when ``make_mining_mesh`` is called (after the launcher has set XLA_FLAGS).
"""

from __future__ import annotations

import os

import jax

from repro.compat import make_mesh


def init_distributed(coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> bool:
    """Initialize ``jax.distributed`` for multi-host mining (DESIGN.md §11).

    Configuration comes from the arguments or, when unset, the standard
    environment variables ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES``
    / ``JAX_PROCESS_ID`` — every worker runs the *same* command line and the
    launcher (SLURM, mpirun, a shell loop) differentiates them by env.  With
    neither set this is a no-op and mining stays single-process (the local
    fallback), so all CLIs can call it unconditionally.

    Must run before any other jax call on each worker; afterwards
    ``jax.devices()`` spans the whole cluster and the mining mesh builders
    below lay shards across hosts transparently.  Returns True when
    multi-process mode was actually initialized.
    """
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None:
        num_processes = int(os.environ.get("JAX_NUM_PROCESSES", "0") or 0)
    if process_id is None:
        process_id = int(os.environ.get("JAX_PROCESS_ID", "-1") or -1)
    if not coordinator or num_processes <= 1:
        return False
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=max(process_id, 0))
    return True


def make_mining_mesh(n_data: int | None = None, n_cand: int = 1):
    """2-D ``(data, cand)`` mining mesh over all devices (DESIGN.md §11).

    ``n_data`` defaults to ``n_devices // n_cand``; the product must equal
    the total device count (every device gets a (transaction-shard,
    candidate-shard) cell).  ``n_cand == 1`` still builds the 2-D mesh — the
    runtime treats a size-1 cand axis as candidate replication, and the
    elastic repartitioner can widen it later without a mesh-name change.
    """
    n_dev = len(jax.devices())
    if n_cand < 1:
        raise ValueError(f"n_cand must be >= 1, got {n_cand}")
    if n_data is None:
        if n_dev % n_cand:
            raise ValueError(f"{n_cand} candidate shards do not divide "
                             f"{n_dev} devices")
        n_data = n_dev // n_cand
    if n_data * n_cand != n_dev:
        raise ValueError(f"mesh split {n_data}x{n_cand} != {n_dev} devices")
    return make_mesh((n_data, n_cand), ("data", "cand"))

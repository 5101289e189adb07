"""The reference of the fault-tolerant loops' bitwise tests.

``caqr_factorize`` compiles the sweep as one program. The scheduled driver
and the elastic controller step the same state machine eagerly, one sweep
point at a time, and the online orchestrator steps it in compiled
segments. A healed run is checked bit for bit against the failure-free
sweep stepped eagerly to completion: the program the scheduled driver runs
when nothing fails."""
from repro.core import CAQRResult
from repro.ft.online.state import finalize, initial_sweep_state, run_steps


def eager_sweep(A, comm, panel_width):
    """``CAQRResult`` (bundles collected) of the state machine stepped
    eagerly to completion."""
    state = run_steps(comm, initial_sweep_state(comm, A, panel_width))
    return CAQRResult(*finalize(comm, state))

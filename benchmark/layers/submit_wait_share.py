"""Share of the submit stage's wall in which its thread did not run: 100 x
(wall - cpu) / wall, the wall summed over the six phases of
`banjax_submit_phase_seconds_total{phase}` and cpu =
`banjax_submit_cpu_seconds_total`, the submitting thread's own CPU clock read
where a batch's stage starts and where it ends.  What the thread waited for in
that share: the interpreter another thread held, the windows lock, a device
sync, a core.  None from a program without the families."""
from benchmark.harness import prom


def read(ctx):
    wall = prom.delta(ctx["prom0"], ctx["prom1"],
                      "banjax_submit_phase_seconds_total")
    cpu = prom.delta(ctx["prom0"], ctx["prom1"],
                     "banjax_submit_cpu_seconds_total")
    if not wall or cpu is None:
        return None
    return 100.0 * (wall - cpu) / wall

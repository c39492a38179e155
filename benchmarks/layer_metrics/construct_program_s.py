"""Layer: construction. Seconds inside the program's `build_model` and
`build_step` spans (the module and its initial variables; the mesh, the plan,
the step programs, the placed state), summed over the run's two trainers.
Moves `setup_s`. Source: program_span."""

from benchmarks import span_reduce


def read(run):
    r = span_reduce.reduced(run)
    if not r or not r["setup"]:
        return None
    s = r["setup"]
    if "build_model" not in s and "build_step" not in s:
        return None
    return s.get("build_model", 0.0) + s.get("build_step", 0.0)

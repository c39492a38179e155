"""Layer: device. Share of the profiled sparse block's device-idle time
during which the host was in none of the program's leaf spans: device busy
intervals from the trace, on the unix clock through the session's
`profile_start_time`; spans through the recording's clock pair. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import span_reduce


def read(run):
    r = span_reduce.reduced(run)
    if not r or not r["idle"] or not r["idle"]["idle_s"]:
        return None
    idle = r["idle"]
    return 100.0 * idle["by_span_s"]["unnamed"] / idle["idle_s"]

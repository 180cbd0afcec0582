"""Arena passes per greedy selection: the ``rows`` that the ``select``
spans report (the arena rows a call's counter build and per-pick
updates read) over their ``theta`` (the arena's row capacity), summed
over every ``select`` span of the run (moves ``select_s``).  A select
run's traced window holds no ``extend`` to place spans by, and every
call reads the same fixed arena, so no span is left out.  A program
whose spans carry no ``rows`` reads nothing."""
from bench import spans


def read(run):
    ev = [e for e in run.spans
          if e.get("name") == "select"
          and {"rows", "theta"} <= set(e.get("args", {}))]
    return spans.ratio([e["args"]["rows"] for e in ev],
                       [e["args"]["theta"] for e in ev])

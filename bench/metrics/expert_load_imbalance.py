"""How unevenly a decode step's pairs fall on the held experts: the
mean over the window's ``rapid.decode`` spans of the most loaded held
expert's pairs over the mean pairs a held expert got, averaged over
the layers (``expert_max``, which sums each layer's most loaded
expert, times the experts held over ``expert_pairs``).  1 is an even
load; the grouped kernel's time follows the most loaded expert.  None
without those spans."""
from bench import program_spans as P


def reduce(run):
    held = len(getattr(run.dims, "held", ()) or ())
    got = [s.attrs for s in P.in_host_span(run, "rapid.decode") or ()
           if s.attrs.get("expert_pairs")]
    if not held or not got:
        return None
    return sum(a["expert_max"] * held / a["expert_pairs"]
               for a in got) / len(got)

"""How much of a commit is the host link's, in per cent: the median
over the saves started inside the window of the time covered by a
save's ``checkpoint/fetch`` spans (one ``np.asarray`` of a piece each,
on the save's first thread) over its ``checkpoint/save`` span (start to
rename).  Source: the job's own spans."""

from perfbench.harness import hostspans


def read(view):
    return hostspans.save_busy_share(view, hostspans.FETCH)

"""How much of a commit is the disk's, in per cent: the median over the
saves started inside the window of the time covered by a save's
``checkpoint/write`` spans (one piece into its file each), both writer
threads taken together, over its ``checkpoint/save`` span (start to
rename).  Source: the job's own spans."""

from perfbench.harness import hostspans


def read(view):
    return hostspans.save_busy_share(view, hostspans.WRITE)

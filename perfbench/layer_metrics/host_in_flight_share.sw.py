"""How much of the host's bound the job used, in per cent: the job's own
counter ``host_in_flight_max_bytes`` (the most bytes of copies to the
host that were asked for and not yet fetched at any moment since the
job was resumed in set-up, snapshots' and a save's pieces' together)
over the configuration's ``host.ahead_bytes``.  A share of a bound: at
most 100 while the guarantee ``host_bound`` holds (``check`` holds every
run to it), and near it the two kinds of copies take each other's room
(``transfer_wait_share.sw`` says what that costs).  A program that keeps
no such counter, or a configuration that states no bound: a printed
reason and nothing."""

NAME = "host_in_flight_max_bytes"


def read(view):
    session = view.session
    job = getattr(session, "job", None)
    stats = job.stats() if hasattr(job, "stats") else {}
    bound = session.ctx.config.get("host", {}).get("ahead_bytes")
    if NAME not in stats or not bound:
        print(f"perfbench: the job keeps no {NAME} or the configuration states "
              "no `host.ahead_bytes`: nothing is reported", flush=True)
        return None
    print(f"perfbench: at most {stats[NAME]} bytes on their way to the host, "
          f"of the {bound} it takes", flush=True)
    return 100.0 * stats[NAME] / bound

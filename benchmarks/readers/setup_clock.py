"""A part of set-up on the host clock: args {"phase": name}.  Nothing when
the run had no such phase."""


def read(run, args):
    return run.phases.get(args["phase"])

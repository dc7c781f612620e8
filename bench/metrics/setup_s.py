"""Process start to the window's opening: imports, building and warming
the executors, the weights, starting the server and the load generator,
and (closed loop) filling every slot."""


def reduce(run):
    return run.setup_s

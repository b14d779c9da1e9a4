"""HPL's quantity: the measured window over the systems solved in it.  One
system is generate, factor, solve, solution ready."""


def value(run):
    solved = len(run.answers)
    return run.window_s / solved if solved else None

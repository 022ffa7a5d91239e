"""Non-padding tokens of every step completed in the window, over the
whole window (first dispatch to the last loss and update ready), all
chips together."""


def read(run):
    win = run['window']
    return win['tokens'] / win['seconds']

"""Wall time of the window, from its start to the end of its last dispatch,
over all the federated rounds completed in it. Host clock."""


def read(run):
    return 1e3 * run.window.round_s

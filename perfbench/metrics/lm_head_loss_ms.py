"""Device time per round under the program's `lm_head_loss` scope: the tied
head's product over the vocabulary, the log-softmax and the loss, forward
and backward. From the device trace, by the scope path of each operation
(`harness.Run.scope_ms`), mean over the chips; reads nothing where no
operation carries the scope."""


def read(run):
    return run.scope_ms("lm_head_loss")

"""Of the device's busy time in the traced window, the log-likelihood
head: every operation that reads or writes an array with the vocabulary's
extent (the head's product a chunk of tokens, its log-sum-exp, the pick of
the target's logit; the embedding's lookup reads such an array too and is
counted with them), told by the shapes in the instruction's text, %."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    vocab = str(int(run["cell"].config["vocab_size"]))

    def has_vocabulary(name: str) -> bool:
        return f",{vocab}]" in name or f"[{vocab}," in name \
            or f"[{vocab}]" in name

    taken = sum(s for _c, s in
                trace.op_seconds(select=has_vocabulary).values())
    busy = trace.busy_seconds() * max(len(trace.device_ops), 1)
    return 100.0 * taken / busy if taken and busy else None

"""train_tokens_per_s: the tokens of every optimizer step in the window over
the wall time from the window's first dispatch to ``block_until_ready`` of
its last step."""


def read(record):
    if record.get("job") != "train":
        return None
    return record["tokens"] / record["window_s"]

"""prefix_hit_rate.serve_online: prompt tokens served from the prefix
cache over all prompt tokens admitted (a count), in percent."""


def read(record):
    if record.get("job") != "serve_online" or not record.get("prompt_tokens"):
        return None
    return 100.0 * record["cached_tokens"] / record["prompt_tokens"]

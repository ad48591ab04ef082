from benchmark.end_to_end_metrics.throughput import per_second_per_chip


def read(run: dict):
    return per_second_per_chip(run, "token")

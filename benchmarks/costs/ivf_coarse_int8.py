"""One call of the int8 centroid scorer of two-stage retrieval
(``ops/retrieval.score_centroids_quantized`` from ``serving/ann._probe_tpu``)
at a padded query bucket: the int8 centroid table and its float32 scale and
bias rows read once, int8 queries read, float32 coarse scores written."""


def cost(bucket: int, n_partitions: int, rank: int) -> dict:
    return {
        "ops": 2 * bucket * n_partitions * rank,
        "bytes": (n_partitions * rank + 2 * 4 * n_partitions + bucket * rank
                  + 4 * bucket + 4 * bucket * n_partitions),
        "ops_peak": "int8_ops_per_s",
    }

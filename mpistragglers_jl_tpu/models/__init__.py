_HOME = {
    "LogisticRegression": "logreg",
    "CodedSGD": "logreg",
    "TransformerConfig": "transformer",
    "init_params": "transformer",
    "param_specs": "transformer",
    "forward_dense": "transformer",
    "make_forward": "transformer",
    "make_train_step": "transformer",
    "make_optax_train_step": "transformer",
    "optax_step": "transformer",
    "shard_params": "transformer",
    "batch_axes": "transformer",
    "data_spec": "transformer",
    "init_cache": "decode",
    "cache_specs": "decode",
    "decode_batch_axes": "decode",
    "shard_cache": "decode",
    "prefill_dense": "decode",
    "decode_step_dense": "decode",
    "decode_step_ring_dense": "decode",
    "generate_dense": "decode",
    "generate_ring_dense": "decode",
    "init_ring_cache": "decode",
    "make_ring_generate": "decode",
    "CodedGradTrainer": "coded_train",
    "transformer_chunk_loss": "coded_train",
    "ring_from_cache": "decode",
    "Request": "serving",
    "ServingScheduler": "serving",
    "make_serving_scan": "serving",
    "serving_decode_step_dense": "serving",
    "PagePool": "paging",
    "PagePoolExhausted": "paging",
    "prefix_page_digests": "paging",
    "RequestRouter": "router",
    "RoutedRequest": "router",
    "ROUTER_POLICIES": "router",
    "PrefillWorker": "disagg",
    "DecodeReplica": "disagg",
    "MigrationPlanner": "disagg",
    "MigrationTicket": "disagg",
    "MigrationRing": "disagg",
    "MigrationRingReader": "disagg",
    "make_prefill": "decode",
    "make_decode_step": "decode",
    "make_extend": "decode",
    "make_generate": "decode",
    "init_moe_layer": "moe",
    "moe_layer_specs": "moe",
    "switch_route": "moe",
    "switch_route_indices": "moe",
    "moe_ffn_dense": "moe",
    "moe_ffn_sharded": "moe",
    "init_topk_layer": "moe",
    "topk_route": "moe",
    "group_tiling": "moe",
    "grouped_matmul": "moe",
    "moe_ffn_topk": "moe",
    "ring_widths": "decode",
}

__all__ = list(_HOME) + ["clear_cached_programs"]


def clear_cached_programs() -> None:
    """Drop every lru-cached jitted program factory in the models
    package (dense generation runners, serving tick/admission
    programs). Compiled programs can pin device buffers;
    long-running hosts that sweep many shapes (benchmarks, services)
    call this between phases to release HBM. One public chokepoint so
    callers cannot silently miss a newly added cache."""
    from . import decode, serving

    for cache in (
        decode._dense_runner,
        decode._grouped_layer,
        serving._fresh_arena,
        serving._serving_scan_paged,
        serving._extend_chunk_dense,
        serving._extend_chunk_group,
        serving._finish_admit_dense,
        serving._seed_admit_paged,
        serving._place_paged,
        serving._copy_pages_paged,
        serving._gather_ring_paged,
    ):
        cache.cache_clear()
    # the one serving program jitted at module level (shapes alone key it)
    serving.serving_reset_arena.clear_cache()


def __getattr__(name):
    # lazy: models pull in jax; keep the core package importable without it
    if name in _HOME:
        import importlib

        mod = importlib.import_module(f".{_HOME[name]}", __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

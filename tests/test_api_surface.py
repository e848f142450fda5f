"""SURVEY.md Appendix B parity contract: every public-API equivalent the
blueprint promises must exist at its documented path. Pure import/hasattr
checks — the behavioral coverage lives in the per-component suites."""

import importlib

import pytest

SURFACE = {
    "apex1_tpu.amp": [
        "Amp", "initialize", "scale_loss", "AmpState"],
    "apex1_tpu.optim": [
        "fused_adam", "fused_lamb", "fused_sgd", "fused_novograd",
        "fused_adagrad", "clip_grad_norm", "clip_grad_norm_"],
    "apex1_tpu.optim.larc": ["larc", "LARC"],
    "apex1_tpu.ops": [
        "layer_norm", "rms_norm", "FusedLayerNorm", "FusedRMSNorm",
        "scaled_masked_softmax", "scaled_upper_triang_masked_softmax",
        "FusedScaleMaskSoftmax", "softmax_cross_entropy_loss",
        "apply_rotary_pos_emb", "rope_tables", "set_impl", "force_impl"],
    "apex1_tpu.ops.fused_dense": [
        "FusedDense", "FusedDenseGeluDense", "MLP", "fused_dense",
        "fused_dense_gelu_dense", "fused_glu", "check_glu_geometry"],
    "apex1_tpu.ops.chunked_loss": [
        "chunked_logprob", "chunked_dpo_loss", "chunked_orpo_loss",
        "chunked_kl_loss", "check_chunk_geometry"],
    "apex1_tpu.ops.lora_epilogue": ["lora_delta", "check_lora_geometry"],
    "apex1_tpu.serving.lora": ["LoraAdapterStore"],
    "apex1_tpu.ops.attention": ["flash_attention", "fmha"],
    "apex1_tpu.ops.stochastic": [
        "fused_bias_dropout_add", "fused_dropout_add_layer_norm",
        "seed_from_key", "fold_seed"],
    "apex1_tpu.ops.linear_xent": ["linear_cross_entropy",
                                  "shard_stats_packed"],
    "apex1_tpu.ops.fused_collective": [
        "fused_matmul_reduce_scatter", "fused_all_gather_matmul",
        "fused_all_gather_matmul_serial", "all_gather_flash_attention",
        "fused_vocab_parallel_merge", "matmul_reduce_scatter_rdma"],
    "apex1_tpu.parallel": [
        "DistributedDataParallel", "SyncBatchNorm",
        "convert_syncbn_model"],
    "apex1_tpu.parallel.distributed_optimizer": [
        "distributed_fused_adam", "distributed_fused_lamb",
        "shard_opt_state_specs", "fsdp_param_specs",
        "flat_param_len", "shard_padded_len", "repack_flat_shard"],
    "apex1_tpu.parallel.ring_attention": ["ring_attention",
                                          "ring_attention_serial"],
    "apex1_tpu.parallel.ulysses": ["ulysses_attention"],
    "apex1_tpu.parallel.halo": ["halo_exchange", "exchange_overlap"],
    "apex1_tpu.testing.hlo_probe": ["optimized_hlo",
                                    "check_collective_overlap",
                                    "assert_collective_overlap"],
    "apex1_tpu.contrib": [
        "fmha", "SelfMultiheadAttn", "EncdecMultiheadAttn",
        "SoftmaxCrossEntropyLoss", "clip_grad_norm_", "GroupBatchNorm2d",
        "GroupNorm", "focal_loss", "index_mul_2d", "TransducerJoint",
        "TransducerLoss", "ASP", "permutation_search",
        "distributed_fused_adam", "distributed_fused_lamb"],
    "apex1_tpu.transformer.parallel_state": [
        "initialize_model_parallel", "destroy_model_parallel",
        "model_parallel_is_initialized", "get_tensor_model_parallel_group",
        "get_pipeline_model_parallel_group", "get_data_parallel_group",
        "get_embedding_group", "is_rank_in_embedding_group",
        "get_tensor_model_parallel_world_size",
        "get_pipeline_model_parallel_world_size",
        "get_tensor_model_parallel_rank",
        "get_pipeline_model_parallel_rank",
        "is_pipeline_first_stage", "is_pipeline_last_stage",
        "set_virtual_pipeline_model_parallel_rank",
        "get_virtual_pipeline_model_parallel_world_size"],
    "apex1_tpu.transformer.tensor_parallel": [
        "ColumnParallelLinear", "RowParallelLinear",
        "VocabParallelEmbedding", "column_parallel_linear",
        "row_parallel_linear", "vocab_parallel_embedding",
        "vocab_parallel_cross_entropy",
        "vocab_parallel_linear_cross_entropy", "checkpoint",
        "model_parallel_seed", "get_rng_tracker", "broadcast_data",
        "copy_to_tensor_model_parallel_region",
        "reduce_from_tensor_model_parallel_region",
        "scatter_to_tensor_model_parallel_region",
        "gather_from_tensor_model_parallel_region",
        "scatter_to_sequence_parallel_region",
        "gather_from_sequence_parallel_region",
        "reduce_scatter_to_sequence_parallel_region",
        "all_gather_matmul", "matmul_reduce_scatter",
        "VocabUtility", "divide", "split_tensor_along_last_dim"],
    "apex1_tpu.transformer.pipeline_parallel": [
        "get_forward_backward_func", "forward_backward_no_pipelining",
        "forward_backward_pipelining_without_interleaving",
        "forward_backward_pipelining_with_interleaving",
        "pipeline_apply", "pipeline_tied_apply",
        "allreduce_embedding_grads", "pipelined_loss_fn",
        "p2p_communication"],
    "apex1_tpu.transformer.microbatches": [
        "build_num_microbatches_calculator"],
    "apex1_tpu.transformer.moe": [
        "MoEConfig", "MoEMLP", "moe_shard_map_apply", "router"],
    "apex1_tpu.fp16_utils": [
        "FP16_Optimizer", "network_to_half",
        "master_params_to_model_params", "prep_param_lists"],
    "apex1_tpu.runtime": [
        "PrefetchLoader", "TokenDataset", "pack_documents",
        "write_token_file", "flatten", "unflatten", "RequestFeeder"],
    "apex1_tpu.serving": [
        "Engine", "EngineConfig", "RequestResult", "Scheduler",
        "Request", "Backpressure", "KVPool", "PagedKVPool",
        "PagedPrefix", "PrefixPage", "RadixIndex", "ngram_propose",
        "ServingMetrics", "RequestRecord"],
    "apex1_tpu.ops.paged_decode": [
        "PagedCache", "cache_attend", "check_paged_geometry",
        "fused_sample", "gather_pages", "paged_attend",
        "paged_update_attend", "sample_token", "scatter_pages"],
    "apex1_tpu.ops.decode_attend": [
        "decode_attend", "check_decode_geometry", "DECODE_BLOCK",
        "MAX_ROWS", "fetch_depth", "FETCH_BYTES"],
    "apex1_tpu.models.generate": [
        "generate", "speculative_generate", "beam_search", "t5_generate",
        "init_cache", "cache_len", "cache_write", "cached_attention",
        "sample_token", "counter_sample", "last_real_logits"],
    "apex1_tpu.core.mesh": [
        "make_mesh", "make_hybrid_mesh", "MeshConfig", "MeshResource",
        "shard_batch", "replicate"],
    "apex1_tpu.core.policy": ["PrecisionPolicy", "get_policy"],
    "apex1_tpu.core.loss_scale": [
        "make_loss_scale", "all_finite", "select_tree"],
    "apex1_tpu.core.capability": [
        "get_capability", "detect_generation", "require", "vmem_budget"],
    "apex1_tpu.checkpoint": [
        "save_checkpoint", "restore_checkpoint", "CheckpointManager"],
    "apex1_tpu.models.gpt2": ["GPT2", "GPT2Config", "gpt2_loss_fn"],
    "apex1_tpu.models.bert": ["BertConfig", "BertPretrain"],
    "apex1_tpu.models.resnet": ["ResNet", "ResNetConfig", "Bottleneck",
                                "SpatialBottleneck"],
    "apex1_tpu.models.llama": ["Llama", "LlamaConfig", "LlamaBlock",
                               "llama_loss_fn"],
    "apex1_tpu.models.llama_3d": [
        "Llama3DConfig", "make_train_step", "build_step",
        "abstract_state", "from_llama_params", "reshape_chunks",
        "combine_grads", "state_template"],
    "apex1_tpu.resilience.reshard": [
        "LayoutMismatch", "reshard_state", "reshard_checkpoint",
        "read_plan", "plan_meta", "mesh_str"],
    "apex1_tpu.resilience.elastic": [
        "ElasticDecision", "elastic_resume", "drill"],
    "apex1_tpu.utils.observability": ["MetricsLogger", "Timers"],
    "apex1_tpu.obs": ["ObsRun", "StopWatch", "default_run", "emit",
                      "read_events", "TraceError", "build_report",
                      "parse_xspace", "write_report"],
    "apex1_tpu.obs.calibrate": [
        "collect_pairs", "fit", "build_calibration", "load_calibration",
        "step_slowdown", "kernel_slowdown", "newest_prediction_path",
        "roofline_ms"],
    "apex1_tpu.testing": [
        "force_virtual_cpu_devices", "enable_persistent_compilation_cache",
        "distributed_mesh", "standalone_gpt",
        "standalone_bert"],
    "apex1_tpu.lint": [
        "lint_paths", "lint_files", "lint_sources", "LintResult",
        "RULES", "RULE_SLUGS"],
    "apex1_tpu.lint.kernels": [
        "check_kernels", "KERNEL_RULES", "KernelRule"],
    "apex1_tpu.lint.protocols": [
        "check_protocols", "PROTOCOL_RULES", "ProtocolRule"],
    "apex1_tpu.vmem_model": [
        "CHECKS", "budget_bytes", "flash_check", "row_check",
        "linear_xent_check", "cm_check", "agf_check", "int8_check",
        "rdma_check", "rdma_slot_bytes", "static_frame_bytes",
        "paged_decode_check", "decode_attend_check", "fused_sample_check",
        "chunked_loss_check", "fused_swiglu_check",
        "lora_epilogue_check"],
    "apex1_tpu.perf_model": [
        "roofline", "kernel_cases", "flash_flops_bytes",
        "linear_xent_flops", "ring_attention_comms",
        "sp_boundary_comms", "allreduce_bytes",
        "kv_cache_bytes", "serving_capacity", "speculative_speedup"],
    "apex1_tpu.autopilot": [
        "Autopilot", "AutopilotConfig", "SLOTarget", "FleetView",
        "ControllerState", "Action", "decide", "default_slo"],
    "apex1_tpu.testing.fleetsim": [
        "VirtualClock", "SimRequest", "Trace", "synthetic_trace",
        "FleetSimConfig", "FleetSim", "SimReport", "run_fleet",
        "kill_k_of_n"],
    "apex1_tpu.planner": [
        "ModelShape", "Layout", "Violation", "BANKED_SHAPES",
        "check_layout", "check_plan_model", "enumerate_layouts",
        "fit_check",
        "hbm_breakdown", "price_layout", "calibration_factor",
        "make_plan", "search_layouts", "PlanError", "plan_json",
        "save_plan", "load_plan", "partition_rules", "rules_to_specs",
        "plan_param_specs", "llama3d_config_from_plan",
        "layout_from_plan", "PLAN_SCHEMA", "PLAN_SPEC_KEYS",
        "plan_for_layout", "plan_spec", "model_shape_from_plan"],
}


@pytest.mark.parametrize("module", sorted(SURFACE))
def test_surface(module):
    mod = importlib.import_module(module)
    missing = [n for n in SURFACE[module] if not hasattr(mod, n)]
    assert not missing, f"{module} missing {missing}"

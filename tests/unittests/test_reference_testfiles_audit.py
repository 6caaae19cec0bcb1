"""Executable audit: every file in the reference's unittest suite
(python/paddle/fluid/tests/unittests/, ~v0.11 snapshot, 199 entries incl. dotfiles) must
map to a ported OpTest-config tranche, an equivalent repo test file, or a
documented skip with a reason (round-4 verdict missing #3 done-gate — the
mirror of test_reference_op_files_audit.py for *tests* instead of *ops*).

The file list is a frozen snapshot so the audit runs without the reference
checkout present; when the checkout IS present the snapshot is re-verified
against the live tree (same contract as the op-file audit).
"""
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
TESTS_ROOT = os.path.dirname(HERE)
REFERENCE_DIR = "/root/reference/python/paddle/fluid/tests/unittests"

# Frozen `ls -a` (minus . ..) of the reference unittest directory
# (199 entries including .gitignore).
REFERENCE_FILES = """
.gitignore CMakeLists.txt __init__.py decorators.py op_test.py
test_accuracy_op.py test_activation_op.py test_adadelta_op.py
test_adagrad_op.py test_adam_op.py test_adamax_op.py
test_array_read_write_op.py test_assign_op.py test_assign_value_op.py
test_auc_op.py test_batch_norm_op.py test_beam_search_decode_op.py
test_beam_search_op.py test_bilinear_tensor_product_op.py
test_bipartite_match_op.py test_box_coder_op.py test_calc_gradient.py
test_cast_op.py test_chunk_eval_op.py test_clip_by_norm_op.py
test_clip_op.py test_compare_op.py test_concat_op.py test_cond_op.py
test_conditional_block.py test_const_value.py test_conv2d_op.py
test_conv2d_transpose_op.py test_conv3d_op.py
test_conv3d_transpose_op.py test_conv_shift_op.py test_cos_sim_op.py
test_create_op_doc_string.py test_crf_decoding_op.py test_crop_op.py
test_cross_entropy_op.py test_ctc_align.py test_cumsum_op.py
test_debugger.py test_decayed_adagrad_op.py test_default_scope_funcs.py
test_detection_map_op.py test_dropout_op.py test_dyn_rnn.py
test_dynrnn_gradient_check.py test_dynrnn_static_input.py
test_edit_distance_op.py test_elementwise_add_op.py
test_elementwise_div_op.py test_elementwise_max_op.py
test_elementwise_min_op.py test_elementwise_mul_op.py
test_elementwise_pow_op.py test_elementwise_sub_op.py test_exception.py
test_executor_and_mul.py test_expand_op.py test_feed_fetch_method.py
test_fetch_var.py test_fill_constant_batch_size_like_op.py
test_fill_constant_op.py test_fill_op.py test_fill_zeros_like_op.py
test_framework_debug_str.py test_ftrl_op.py test_gather_op.py
test_gaussian_random_batch_size_like_op.py test_gaussian_random_op.py
test_get_places_op.py test_gru_op.py test_gru_unit_op.py
test_hinge_loss_op.py test_huber_loss_op.py test_im2sequence_op.py
test_image_classification_layer.py test_infer_shape.py
test_inference_model_io.py test_initializer.py test_iou_similarity_op.py
test_is_empty_op.py test_l1_norm_op.py test_label_smooth_op.py
test_layer_norm_op.py test_layers.py test_learning_rate_scheduler.py
test_linear_chain_crf_op.py test_lod_array_length_op.py
test_lod_rank_table.py test_lod_reset_op.py test_lod_tensor_array.py
test_lod_tensor_array_ops.py test_log_loss_op.py test_logical_op.py
test_lookup_table_op.py test_lrn_op.py test_lstm_op.py
test_lstm_unit_op.py test_lstmp_op.py test_margin_rank_loss_op.py
test_math_op_patch.py test_matmul_op.py test_maxout_op.py
test_mean_op.py test_memory_optimization_transpiler.py
test_mine_hard_examples_op.py test_minus_op.py
test_modified_huber_loss_op.py test_momentum_op.py test_mul_op.py
test_multi_pass_reader.py test_multiclass_nms_op.py
test_multihead_attention.py test_multiple_reader.py
test_multiplex_op.py test_nce.py test_net.py test_norm_op.py
test_normalization_wrapper.py test_nvprof.py test_one_hot_op.py
test_op_support_gpu.py test_operator.py test_operator_desc.py
test_optimizer.py test_pad_op.py test_parallel_op.py test_parameter.py
test_pool2d_op.py test_pool3d_op.py test_pool_max_op.py
test_positive_negative_pair_op.py test_precision_recall_op.py
test_prelu_op.py test_print_op.py test_prior_box_op.py
test_profiler.py test_program.py test_protobuf.py
test_protobuf_descs.py test_proximal_adagrad_op.py
test_proximal_gd_op.py test_rank_loss_op.py test_recordio_reader.py
test_recurrent_op.py test_recv_op.py test_reduce_op.py
test_registry.py test_regularizer.py test_reorder_lod_tensor.py
test_reshape_op.py test_rmsprop_op.py test_rnn_memory_helper_op.py
test_roi_pool_op.py test_row_conv_op.py test_scale_op.py
test_scatter_op.py test_scope.py test_selected_rows.py
test_seq_concat_op.py test_seq_conv.py test_seq_pool.py
test_sequence_erase_op.py test_sequence_expand.py
test_sequence_reshape.py test_sequence_slice_op.py
test_sequence_softmax_op.py test_sgd_op.py test_shrink_rnn_memory.py
test_sigmoid_cross_entropy_with_logits_op.py test_sign_op.py
test_smooth_l1_loss_op.py test_softmax_op.py
test_softmax_with_cross_entropy_op.py
test_split_and_merge_lod_tensor_op.py test_split_op.py
test_split_selected_rows_op.py test_split_var.py test_spp_op.py
test_squared_l2_distance_op.py test_squared_l2_norm_op.py
test_sum_op.py test_switch.py test_target_assign_op.py test_tensor.py
test_top_k_op.py test_transpose_op.py
test_uniform_random_batch_size_like_op.py test_uniform_random_op.py
test_unique_name.py test_unpool_op.py test_variable.py
test_warpctc_op.py test_weight_normalization.py test_while_op.py
""".split()

# --- disposition 1: ported as reference-OpTest-config tranches -------------
# (tests/unittests/test_ref_opconfigs*.py re-run the reference tests'
# attr/shape grids through the real executor path vs numpy references)
T1 = "unittests/test_ref_opconfigs.py"
T2 = "unittests/test_ref_opconfigs2.py"
T3 = "unittests/test_ref_opconfigs3.py"
T4 = "unittests/test_ref_opconfigs4.py"
T5 = "unittests/test_ref_opconfigs5.py"
T6 = "unittests/test_ref_opconfigs6.py"

TRANCHE = {
    "test_activation_op.py": T1,
    "test_adam_op.py": T4,
    "test_batch_norm_op.py": T2,
    "test_box_coder_op.py": T5,
    "test_cast_op.py": T3,
    "test_clip_by_norm_op.py": T4,
    "test_clip_op.py": T1,
    "test_compare_op.py": T4,
    "test_concat_op.py": T1,
    "test_conv2d_op.py": T1,
    "test_conv2d_transpose_op.py": T1,
    "test_cos_sim_op.py": T3,
    "test_crop_op.py": T2,
    "test_cross_entropy_op.py": T1,
    "test_cumsum_op.py": T1,
    "test_dropout_op.py": T1,
    "test_edit_distance_op.py": T1,
    "test_elementwise_add_op.py": T1,
    "test_elementwise_div_op.py": T1,
    "test_elementwise_max_op.py": T1,
    "test_elementwise_min_op.py": T1,
    "test_elementwise_mul_op.py": T1,
    "test_elementwise_pow_op.py": T1,
    "test_elementwise_sub_op.py": T1,
    "test_expand_op.py": T2,
    "test_ftrl_op.py": T4,
    "test_gather_op.py": T1,
    "test_gaussian_random_batch_size_like_op.py": T3,
    "test_gaussian_random_op.py": T3,
    "test_gru_op.py": T3,
    "test_gru_unit_op.py": T4,
    "test_hinge_loss_op.py": T3,
    "test_huber_loss_op.py": T3,
    "test_im2sequence_op.py": T2,
    "test_is_empty_op.py": T3,
    "test_label_smooth_op.py": T3,
    "test_layer_norm_op.py": T2,
    "test_lod_reset_op.py": T3,
    "test_log_loss_op.py": T3,
    "test_logical_op.py": T4,
    "test_lookup_table_op.py": T1,
    "test_lrn_op.py": T1,
    "test_lstm_op.py": T3,
    "test_lstm_unit_op.py": T4,
    "test_margin_rank_loss_op.py": T3,
    "test_matmul_op.py": T1,
    "test_maxout_op.py": T1,
    "test_mine_hard_examples_op.py": T5,
    "test_mul_op.py": T1,
    "test_multiclass_nms_op.py": T5,
    "test_multiplex_op.py": T3,
    "test_one_hot_op.py": T1,
    "test_pad_op.py": T2,
    "test_pool2d_op.py": T1,
    "test_prelu_op.py": T2,
    "test_prior_box_op.py": T5,
    "test_rank_loss_op.py": T3,
    "test_reduce_op.py": T1,
    "test_rmsprop_op.py": T4,
    "test_row_conv_op.py": T2,
    "test_scale_op.py": T4,
    "test_scatter_op.py": T1,
    "test_seq_concat_op.py": T3,
    "test_seq_pool.py": T1,
    "test_sequence_expand.py": T1,
    "test_sequence_slice_op.py": T3,
    "test_sequence_softmax_op.py": T3,
    "test_sign_op.py": T3,
    "test_smooth_l1_loss_op.py": T2,
    "test_softmax_op.py": T1,
    "test_softmax_with_cross_entropy_op.py": T3,
    "test_split_op.py": T1,
    "test_sum_op.py": T1,
    "test_target_assign_op.py": T5,
    "test_top_k_op.py": T4,
    "test_transpose_op.py": T1,
    "test_uniform_random_batch_size_like_op.py": T3,
    "test_uniform_random_op.py": T3,
    "test_accuracy_op.py": T6,
    "test_assign_value_op.py": T6,
    "test_fill_constant_batch_size_like_op.py": T6,
    "test_mean_op.py": T6,
    "test_minus_op.py": T6,
    "test_norm_op.py": T6,
    "test_reshape_op.py": T6,
    "test_sequence_erase_op.py": T6,
    "test_squared_l2_distance_op.py": T6,
}

# --- disposition 2: equivalent repo test file(s) ---------------------------
# Paths relative to tests/; each named file must exist (asserted below).
U = "unittests/"
B = "book/"
EQUIV = {
    "op_test.py": [U + "op_test.py"],
    "test_adadelta_op.py": [U + "test_optimizer_numeric.py"],
    "test_adagrad_op.py": [U + "test_optimizer_numeric.py"],
    "test_adamax_op.py": [U + "test_optimizer_numeric.py"],
    "test_array_read_write_op.py": [U + "test_control_flow.py"],
    "test_assign_op.py": [U + "test_loss_misc_ops.py",
                          U + "test_ref_opconfigs6.py"],
    "test_auc_op.py": [U + "test_metrics_auc.py"],
    "test_beam_search_decode_op.py": [U + "test_control_flow.py",
                                      B + "test_machine_translation.py"],
    "test_beam_search_op.py": [U + "test_control_flow.py",
                               B + "test_machine_translation.py"],
    "test_bilinear_tensor_product_op.py": [U + "test_tail_ops.py"],
    "test_bipartite_match_op.py": [U + "test_detection_ops.py"],
    "test_calc_gradient.py": [U + "test_calc_gradient_weight_norm.py"],
    "test_chunk_eval_op.py": [U + "test_crf_ops.py"],
    "test_cond_op.py": [U + "test_control_flow.py"],
    "test_conditional_block.py": [U + "test_control_flow.py"],
    "test_conv3d_op.py": [U + "test_volumetric_ops.py"],
    "test_conv3d_transpose_op.py": [U + "test_volumetric_ops.py"],
    "test_conv_shift_op.py": [U + "test_program_fuzz.py",
                              U + "test_tail_ops.py"],
    "test_crf_decoding_op.py": [U + "test_crf_ops.py"],
    "test_ctc_align.py": [U + "test_ctc_ops.py"],
    "test_debugger.py": [U + "test_aux_modules.py"],
    "test_decayed_adagrad_op.py": [U + "test_optimizer_numeric.py"],
    "test_default_scope_funcs.py": [U + "test_aux_modules.py"],
    "test_detection_map_op.py": [U + "test_aux_modules.py",
                                 U + "test_tail_ops.py"],
    "test_dyn_rnn.py": [U + "test_control_flow.py",
                        U + "test_rnn_numeric.py"],
    "test_dynrnn_gradient_check.py": [U + "test_control_flow.py"],
    "test_dynrnn_static_input.py": [U + "test_control_flow.py"],
    "test_exception.py": [U + "test_checkpoint_and_errors.py"],
    "test_executor_and_mul.py": [U + "test_ops_numeric.py",
                                 U + "test_fit_a_line.py"],
    "test_feed_fetch_method.py": [U + "test_api_surface_extras.py"],
    "test_fetch_var.py": [U + "test_aux_modules.py",
                          U + "test_api_surface_extras.py"],
    "test_fill_constant_op.py": [U + "test_program_prune.py",
                                 U + "test_ops_coverage.py"],
    "test_fill_op.py": [U + "test_volumetric_ops.py"],
    "test_fill_zeros_like_op.py": [U + "test_loss_misc_ops.py"],
    "test_framework_debug_str.py": [U + "test_api_surface_extras.py",
                                    U + "test_program_tooling_zoo.py"],
    "test_image_classification_layer.py": [U + "test_image_models.py"],
    "test_infer_shape.py": [U + "test_program_fuzz.py"],
    "test_inference_model_io.py": [U + "test_inference_model.py"],
    "test_initializer.py": [U + "test_regularizer_clip_init.py"],
    "test_iou_similarity_op.py": [U + "test_detection_ops.py"],
    "test_l1_norm_op.py": [U + "test_tail_ops.py"],
    "test_layers.py": [U + "test_reference_api_parity.py",
                       U + "test_fit_a_line.py",
                       U + "test_api_surface_extras.py"],
    "test_learning_rate_scheduler.py": [U + "test_lr_scheduler.py"],
    "test_linear_chain_crf_op.py": [U + "test_crf_ops.py"],
    "test_lod_array_length_op.py": [U + "test_control_flow.py"],
    "test_lod_rank_table.py": [U + "test_rank_table_ops.py"],
    "test_lod_tensor_array.py": [U + "test_tensor_array_capacity.py"],
    "test_lod_tensor_array_ops.py": [U + "test_control_flow.py",
                                     U + "test_rank_table_ops.py"],
    "test_lstmp_op.py": [U + "test_rnn_numeric.py"],
    "test_math_op_patch.py": [U + "test_math_op_patch.py"],
    "test_memory_optimization_transpiler.py": [U + "test_aux_modules.py",
                                               U + "test_native_graph.py"],
    "test_modified_huber_loss_op.py": [U + "test_tail_ops.py"],
    "test_momentum_op.py": [U + "test_optimizer_numeric.py"],
    "test_multi_pass_reader.py": [U + "test_reader_layers.py"],
    "test_multihead_attention.py": [B + "test_transformer.py",
                                    U + "test_long_context_training.py"],
    "test_multiple_reader.py": [U + "test_reader_layers.py"],
    "test_nce.py": [U + "test_ops_coverage.py"],
    "test_net.py": [U + "test_nets_composites.py"],
    "test_normalization_wrapper.py": [
        U + "test_calc_gradient_weight_norm.py",
        U + "test_ops_coverage.py"],
    "test_operator.py": [U + "test_api_surface_extras.py"],
    "test_operator_desc.py": [U + "test_program_tooling_zoo.py"],
    "test_optimizer.py": [U + "test_optimizer_numeric.py"],
    "test_parallel_op.py": [U + "test_api_parity_shims.py",
                            U + "test_program_parallelism.py"],
    "test_parameter.py": [U + "test_regularizer_clip_init.py",
                          U + "test_program_tooling_zoo.py"],
    "test_pool3d_op.py": [U + "test_volumetric_ops.py"],
    "test_pool_max_op.py": [U + "test_tail_ops.py"],
    "test_positive_negative_pair_op.py": [U + "test_tail_ops.py"],
    "test_precision_recall_op.py": [U + "test_tail_ops.py"],
    "test_print_op.py": [U + "test_api_parity_shims.py"],
    "test_profiler.py": [U + "test_profiler_and_io_data.py"],
    "test_program.py": [U + "test_program_prune.py",
                        U + "test_program_tooling_zoo.py"],
    "test_protobuf_descs.py": [U + "test_program_tooling_zoo.py"],
    "test_proximal_adagrad_op.py": [U + "test_tail_ops.py"],
    "test_proximal_gd_op.py": [U + "test_tail_ops.py"],
    "test_recordio_reader.py": [U + "test_recordio.py"],
    "test_recurrent_op.py": [U + "test_control_flow.py"],
    "test_recv_op.py": [U + "test_distribute_transpiler.py"],
    "test_registry.py": [U + "test_ops_coverage.py"],
    "test_regularizer.py": [U + "test_regularizer_clip_init.py"],
    "test_reorder_lod_tensor.py": [U + "test_rank_table_ops.py"],
    "test_roi_pool_op.py": [U + "test_tail_ops.py"],
    "test_scope.py": [U + "test_checkpoint_and_errors.py",
                      U + "test_aux_modules.py"],
    "test_seq_conv.py": [U + "test_sequence_ops.py",
                         U + "test_sequence_deep.py"],
    "test_sequence_reshape.py": [U + "test_sequence_deep.py"],
    "test_sgd_op.py": [U + "test_optimizer_numeric.py"],
    "test_shrink_rnn_memory.py": [U + "test_rank_table_ops.py"],
    "test_sigmoid_cross_entropy_with_logits_op.py": [
        U + "test_ops_coverage.py",
        U + "test_torch_crossval.py"],
    "test_split_and_merge_lod_tensor_op.py": [U + "test_control_flow.py"],
    "test_split_var.py": [U + "test_distribute_transpiler.py"],
    "test_spp_op.py": [U + "test_tail_ops.py"],
    "test_squared_l2_norm_op.py": [U + "test_tail_ops.py"],
    "test_switch.py": [U + "test_control_flow.py"],
    "test_tensor.py": [U + "test_sequence_deep.py"],
    "test_unique_name.py": [U + "test_aux_modules.py"],
    "test_unpool_op.py": [U + "test_tail_ops.py"],
    "test_variable.py": [U + "test_api_surface_extras.py"],
    "test_warpctc_op.py": [U + "test_ctc_ops.py"],
    "test_weight_normalization.py": [
        U + "test_calc_gradient_weight_norm.py"],
    "test_while_op.py": [U + "test_control_flow.py"],
}

# --- disposition 3: documented skips ---------------------------------------
SKIP = {
    ".gitignore": "VCS metadata, not a test",
    "CMakeLists.txt": "build-system file, not a test",
    "__init__.py": "package marker, not a test",
    "decorators.py": "reference test-harness helper (@prog_scope); the "
                     "repo uses pytest fixtures + program_guard instead",
    "test_const_value.py": "asserts C++ core string constants "
                           "(kEmptyVarName etc.) exist; the TPU design "
                           "has no C++ scope-name constants — the "
                           "framework surface is audited by "
                           "test_reference_api_parity.py",
    "test_create_op_doc_string.py": "asserts the C++ OpProto doc-string "
                                    "machinery; lowering rules are "
                                    "Python (docstrings native), no "
                                    "OpProto exists by design",
    "test_nvprof.py": "CUDA nvprof integration; CUDA-only by "
                      "definition. The profiler bridge equivalent is "
                      "tested in test_profiler_and_io_data.py",
    "test_op_support_gpu.py": "queries the C++ registry for GPU "
                              "kernels; no GPU in the design — "
                              "places.is_compiled_with_cuda() is "
                              "False-by-contract (places.py)",
    "test_protobuf.py": "smoke-tests the protobuf *runtime* the "
                        "reference links against; this framework has "
                        "no protobuf dependency (reference_format.py "
                        "parses the wire format directly, covered by "
                        "test_reference_model_load.py)",
    "test_rnn_memory_helper_op.py": "rnn_memory_helper is the "
                                    "reference's manual RNN-state "
                                    "plumbing; lax.scan carries state "
                                    "natively (subsumed — see the op "
                                    "audit NAME_SUBSUMED)",
    "test_selected_rows.py": "SelectedRows is the reference's sparse "
                             "gradient carrier; gradients are dense "
                             "by design on TPU (SURVEY §6: pserver "
                             "sparse updates become dense sharded "
                             "updates), lookup_table grads verified "
                             "dense in test_ref_opconfigs.py",
    "test_split_selected_rows_op.py": "SelectedRows splitting for the "
                                      "pserver path; see "
                                      "test_selected_rows.py skip — "
                                      "the split *policy* equivalents "
                                      "are tested in "
                                      "test_distribute_transpiler.py",
    "test_get_places_op.py": "get_places is a CPU/GPU device-count op "
                             "feeding ParallelDo; device enumeration "
                             "is jax.devices() (ParallelDo itself is "
                             "tested in test_control_flow.py)",
}


ALL_DISPOSED = set(TRANCHE) | set(EQUIV) | set(SKIP)


def test_every_reference_test_file_is_accounted_for():
    missing = sorted(set(REFERENCE_FILES) - ALL_DISPOSED)
    assert not missing, (
        "reference unittest files with no port/equivalent/skip: %s"
        % missing)


def test_no_unknown_or_double_disposition():
    unknown = sorted(ALL_DISPOSED - set(REFERENCE_FILES))
    assert not unknown, "dispositions for nonexistent files: %s" % unknown
    for a, b in (("TRANCHE", "EQUIV"), ("TRANCHE", "SKIP"),
                 ("EQUIV", "SKIP")):
        overlap = set(globals()[a]) & set(globals()[b])
        assert not overlap, (a, b, sorted(overlap))


def test_mapped_repo_files_exist():
    missing = []
    for targets in list(EQUIV.values()) + [[t] for t in TRANCHE.values()]:
        for rel in targets:
            if not os.path.exists(os.path.join(TESTS_ROOT, rel)):
                missing.append(rel)
    assert not missing, "mapped repo test files missing: %s" % sorted(
        set(missing))


def test_frozen_snapshot_matches_reference_tree():
    """Re-verify the frozen list against the live reference checkout when
    present (the audit itself must not rot)."""
    if not os.path.isdir(REFERENCE_DIR):
        pytest.skip("reference checkout not present")
    # ignore derived/editor artifacts (__pycache__, *.pyc, swap files)
    # so transient junk in the read-only checkout can't fail the audit
    live = sorted(
        n for n in os.listdir(REFERENCE_DIR)
        if n != "__pycache__" and not n.endswith((".pyc", ".swp", "~")))
    assert live == sorted(REFERENCE_FILES), {
        "only_in_live": sorted(set(live) - set(REFERENCE_FILES)),
        "only_in_frozen": sorted(set(REFERENCE_FILES) - set(live))}


# token the op-centric reference file must be traceable by, where the
# obvious strip("test_", "_op.py") doesn't match our naming
_OP_TOKEN_ALIASES = {
    "test_recv_op.py": "pserver",
    "test_assign_op.py": "assign",
    "test_proximal_adagrad_op.py": "Proximal",
    "test_proximal_gd_op.py": "Proximal",
    "test_elementwise_div_op.py": "elementwise_div",
    "test_elementwise_max_op.py": "elementwise_max",
    "test_elementwise_min_op.py": "elementwise_min",
    "test_elementwise_pow_op.py": "elementwise_pow",
    "test_elementwise_sub_op.py": "elementwise_sub",
    "test_top_k_op.py": "topk",
    "test_pool_max_op.py": "max_pool2d_with_index",
    "test_seq_concat_op.py": "sequence_concat",
    "test_seq_conv.py": "sequence_conv",
    "test_seq_pool.py": "sequence_pool",
    "test_ctc_align.py": "ctc_align",
    "test_nce.py": "nce",
    "test_smooth_l1_loss_op.py": "smooth_l1",
    "test_activation_op.py": "relu",
    "test_compare_op.py": "less_than",
    "test_logical_op.py": "logical_and",
    "test_reduce_op.py": "reduce_sum",
    "test_fill_op.py": '"fill"',
    "test_norm_op.py": '"norm"',
    "test_conditional_block.py": "IfElse",
    "test_cond_op.py": "IfElse",
    "test_recurrent_op.py": "StaticRNN",
    "test_parallel_op.py": "ParallelDo",
    "test_multihead_attention.py": "fused_attention",
    "test_while_op.py": "While",
    "test_switch.py": "Switch",
    "test_lod_rank_table.py": "lod_rank_table",
    "test_shrink_rnn_memory.py": "shrink_memory",
    "test_reorder_lod_tensor.py": "reorder_lod_tensor_by_rank",
    "test_split_and_merge_lod_tensor_op.py": "IfElse",
    "test_array_read_write_op.py": "array_write",
    "test_beam_search_op.py": "beam_search",
    "test_beam_search_decode_op.py": "beam_search",
    "test_lod_array_length_op.py": "array_length",
    "test_lod_tensor_array_ops.py": "lod_tensor_to_array",
    "test_dyn_rnn.py": "DynamicRNN",
    "test_dynrnn_gradient_check.py": "DynamicRNN",
    "test_dynrnn_static_input.py": "DynamicRNN",
    "test_warpctc_op.py": "warpctc",
    "test_linear_chain_crf_op.py": "linear_chain_crf",
    "test_crf_decoding_op.py": "crf_decoding",
    "test_chunk_eval_op.py": "chunk_eval",
    "test_detection_map_op.py": "detection_map",
    "test_iou_similarity_op.py": "iou_similarity",
    "test_bipartite_match_op.py": "bipartite",
    "test_roi_pool_op.py": "roi_pool",
    "test_sequence_erase_op.py": "sequence_erase",
    "test_gaussian_random_batch_size_like_op.py":
        "gaussian_random_batch_size_like",
    "test_uniform_random_batch_size_like_op.py": "random_batch_size_like",
    "test_fill_constant_batch_size_like_op.py":
        "fill_constant_batch_size_like",
    "test_sigmoid_cross_entropy_with_logits_op.py":
        "sigmoid_cross_entropy",
    "test_softmax_with_cross_entropy_op.py": "softmax_with_cross_entropy",
    "test_lstm_unit_op.py": "lstm_unit",
    "test_gru_unit_op.py": "gru_unit",
    "test_lstmp_op.py": "lstmp",
    "test_math_op_patch.py": "math_op_patch",
    "test_calc_gradient.py": "calc_gradient",
    "test_weight_normalization.py": "WeightNorm",
    "test_normalization_wrapper.py": "l2_normalize",
    "test_multiplex_op.py": "multiplex",
    "test_im2sequence_op.py": "im2sequence",
    "test_row_conv_op.py": "row_conv",
    "test_one_hot_op.py": "one_hot",
    "test_edit_distance_op.py": "edit_distance",
    "test_mine_hard_examples_op.py": "mine_hard_examples",
    "test_multiclass_nms_op.py": "multiclass_nms",
    "test_target_assign_op.py": "target_assign",
    "test_prior_box_op.py": "prior_box",
    "test_box_coder_op.py": "box_coder",
    "test_label_smooth_op.py": "label_smooth",
    "test_margin_rank_loss_op.py": "margin_rank_loss",
    "test_modified_huber_loss_op.py": "modified_huber",
    "test_huber_loss_op.py": "huber",
    "test_hinge_loss_op.py": "hinge",
    "test_rank_loss_op.py": "rank_loss",
    "test_log_loss_op.py": "log_loss",
    "test_cos_sim_op.py": "cos_sim",
    "test_clip_by_norm_op.py": "clip_by_norm",
    "test_squared_l2_distance_op.py": "squared_l2_distance",
    "test_squared_l2_norm_op.py": "squared_l2_norm",
    "test_l1_norm_op.py": "l1_norm",
    "test_conv_shift_op.py": "conv_shift",
    "test_bilinear_tensor_product_op.py": "bilinear_tensor_product",
    "test_positive_negative_pair_op.py": "positive_negative",
    "test_precision_recall_op.py": "precision_recall",
    "test_spp_op.py": '"spp"',
    "test_unpool_op.py": "unpool",
    "test_maxout_op.py": "maxout",
    "test_lod_reset_op.py": "lod_reset",
    "test_sequence_expand.py": "sequence_expand",
    "test_sequence_reshape.py": "sequence_reshape",
    "test_sequence_slice_op.py": "sequence_slice",
    "test_sequence_softmax_op.py": "sequence_softmax",
    "test_lookup_table_op.py": "lookup_table",
    "test_decayed_adagrad_op.py": "decayed_adagrad",
}


def test_op_file_mappings_actually_mention_the_op():
    """Every TRANCHE/EQUIV mapping for an op-centric reference test file
    must point at repo files at least one of which MENTIONS the op — the
    guard against substring-grep citation errors (two were found by
    hand: nce and roi_pool pointed at files that never test them)."""
    missing = []
    for ref_file in sorted(set(TRANCHE) | set(EQUIV)):
        if not (ref_file.endswith("_op.py") or ref_file in
                _OP_TOKEN_ALIASES):
            continue
        token = _OP_TOKEN_ALIASES.get(
            ref_file, ref_file[len("test_"):-len("_op.py")])
        targets = ([TRANCHE[ref_file]] if ref_file in TRANCHE
                   else EQUIV[ref_file])
        found = False
        for rel in targets:
            with open(os.path.join(TESTS_ROOT, rel)) as f:
                # quoted aliases ('"fill"') force a literal quoted-string
                # match — stripping them would let unrelated identifiers
                # (fill_constant_batch_size_like) satisfy the check
                if token in f.read():
                    found = True
                    break
        if not found:
            missing.append((ref_file, token, targets))
    assert not missing, "mappings that never mention their op: %s" % missing


# --------------------------------------------------------------------------
# The REST of the reference test tree (python/paddle/fluid/tests/ beyond
# unittests/): top-level tests, the book chapters, the memory-optimization
# book variants, and the demo. Same three dispositions.
# --------------------------------------------------------------------------

REFERENCE_TREE_FILES = """
.gitignore book/.gitignore CMakeLists.txt __init__.py notest_concurrency.py test_concurrency.py
test_cpp_reader.py test_data_feeder.py test_detection.py
test_error_clip.py test_gradient_clip.py test_mnist_if_else_op.py
test_python_operator_overriding.py
book/CMakeLists.txt book/__init__.py book/notest_rnn_encoder_decoer.py
book/test_fit_a_line.py book/test_image_classification.py
book/test_label_semantic_roles.py book/test_machine_translation.py
book/test_recognize_digits.py book/test_recommender_system.py
book/test_understand_sentiment.py book/test_word2vec.py
book_memory_optimization/CMakeLists.txt
book_memory_optimization/test_memopt_fit_a_line.py
book_memory_optimization/test_memopt_image_classification_train.py
book_memory_optimization/test_memopt_machine_translation.py
demo/fc_gan.py
""".split()

TREE_EQUIV = {
    "test_cpp_reader.py": [U + "test_recordio.py",
                           U + "test_reader_layers.py"],
    "test_data_feeder.py": [U + "test_sequence_ops.py",
                            U + "test_api_surface_extras.py"],
    "test_detection.py": [U + "test_detection_ops.py"],
    "test_error_clip.py": [U + "test_api_surface_extras.py"],
    "test_gradient_clip.py": [U + "test_regularizer_clip_init.py"],
    "test_mnist_if_else_op.py": [U + "test_control_flow.py"],
    "test_python_operator_overriding.py": [U + "test_math_op_patch.py"],
    "book/test_fit_a_line.py": [U + "test_fit_a_line.py"],
    "book/test_image_classification.py": [U + "test_image_models.py",
                                          B + "test_recognize_digits.py"],
    "book/test_label_semantic_roles.py": [
        B + "test_label_semantic_roles.py"],
    "book/test_machine_translation.py": [B + "test_machine_translation.py"],
    "book/test_recognize_digits.py": [B + "test_recognize_digits.py"],
    "book/test_recommender_system.py": [B + "test_recommender_system.py"],
    "book/test_understand_sentiment.py": [
        B + "test_understand_sentiment.py"],
    "book/test_word2vec.py": [B + "test_word2vec.py"],
    "book/notest_rnn_encoder_decoer.py": [
        B + "test_machine_translation.py"],
    "book_memory_optimization/test_memopt_fit_a_line.py": [
        U + "test_aux_modules.py"],
    "book_memory_optimization/test_memopt_image_classification_train.py": [
        U + "test_aux_modules.py", U + "test_loop_recompute.py"],
    "book_memory_optimization/test_memopt_machine_translation.py": [
        U + "test_aux_modules.py"],
    "demo/fc_gan.py": [B + "test_fc_gan.py"],
}

TREE_SKIP = {
    ".gitignore": "VCS metadata",
    "book/.gitignore": "VCS metadata",
    "CMakeLists.txt": "build-system file",
    "__init__.py": "package marker",
    "book/CMakeLists.txt": "build-system file",
    "book/__init__.py": "package marker",
    "book_memory_optimization/CMakeLists.txt": "build-system file",
    "test_concurrency.py": "fluid.concurrency (Go channels) is a "
                           "documented SURVEY §2 scope cut; "
                           "concurrency.py carries curated "
                           "NotImplementedError stubs",
    "notest_concurrency.py": "disabled in the reference itself; same "
                             "concurrency scope cut",
}


def test_rest_of_reference_tree_accounted_for():
    disposed = set(TREE_EQUIV) | set(TREE_SKIP)
    missing = sorted(set(REFERENCE_TREE_FILES) - disposed)
    unknown = sorted(disposed - set(REFERENCE_TREE_FILES))
    assert not missing, "unaccounted tree files: %s" % missing
    assert not unknown, "dispositions for nonexistent files: %s" % unknown
    overlap = set(TREE_EQUIV) & set(TREE_SKIP)
    assert not overlap, overlap


def test_tree_equiv_targets_exist():
    missing = [rel for targets in TREE_EQUIV.values() for rel in targets
               if not os.path.exists(os.path.join(TESTS_ROOT, rel))]
    assert not missing, sorted(set(missing))


def test_tree_snapshot_matches_reference():
    root = os.path.dirname(REFERENCE_DIR)
    if not os.path.isdir(root):
        pytest.skip("reference checkout not present")
    live = []
    for base, rel in ((root, ""), (os.path.join(root, "book"), "book/"),
                      (os.path.join(root, "book_memory_optimization"),
                       "book_memory_optimization/"),
                      (os.path.join(root, "demo"), "demo/")):
        if not os.path.isdir(base):
            continue   # a missing dir shows up as only_frozen entries
        for n in os.listdir(base):
            # directories are excluded by isfile; only junk filtered here
            if os.path.isfile(os.path.join(base, n)) and \
                    not n.endswith((".pyc", ".swp", "~")):
                live.append(rel + n)
    assert sorted(live) == sorted(REFERENCE_TREE_FILES), {
        "only_live": sorted(set(live) - set(REFERENCE_TREE_FILES)),
        "only_frozen": sorted(set(REFERENCE_TREE_FILES) - set(live))}


# --------------------------------------------------------------------------
# The reference's python/paddle/v2/tests/ (the legacy-API test suite the
# v2 compat shim answers to). Same dispositions.
# --------------------------------------------------------------------------

V2_TEST_FILES = """
CMakeLists.txt cat.jpg test_data_feeder.py test_image.py test_layer.py
test_op.py test_paramconf_order.py test_parameters.py test_rnn_layer.py
test_topology.py
""".split()

V2_EQUIV = {
    "test_data_feeder.py": [U + "test_api_surface_extras.py",
                            B + "test_recognize_digits_v2.py"],
    "test_image.py": [U + "test_v2_image.py"],
    "test_layer.py": [U + "test_v2_layer_vocabulary.py"],
    "test_op.py": [U + "test_api_parity_shims.py"],
    "test_parameters.py": [U + "test_v2_image.py",
                           B + "test_recognize_digits_v2.py"],
    "test_rnn_layer.py": [U + "test_v2_layer_vocabulary.py"],
    "test_topology.py": [B + "test_recognize_digits_v2.py"],
}

V2_SKIP = {
    "CMakeLists.txt": "build-system file",
    "cat.jpg": "test image asset for v2 test_image; the repo's image "
               "tests synthesize arrays (zero-egress fixtures)",
    "test_paramconf_order.py": "asserts the ordering of trainer_config "
                               "protobuf parameter messages; the v2 shim "
                               "builds fluid Programs directly, so no "
                               "paramconf proto exists (SURVEY §2 "
                               "trainer_config_helpers cut)",
}


def test_v2_tests_accounted_for():
    disposed = set(V2_EQUIV) | set(V2_SKIP)
    assert sorted(set(V2_TEST_FILES)) == sorted(disposed), {
        "missing": sorted(set(V2_TEST_FILES) - disposed),
        "unknown": sorted(disposed - set(V2_TEST_FILES))}
    assert not set(V2_EQUIV) & set(V2_SKIP)
    missing = [rel for targets in V2_EQUIV.values() for rel in targets
               if not os.path.exists(os.path.join(TESTS_ROOT, rel))]
    assert not missing, sorted(set(missing))


def test_v2_snapshot_matches_reference():
    d = "/root/reference/python/paddle/v2/tests"
    if not os.path.isdir(d):
        pytest.skip("reference checkout not present")
    live = sorted(n for n in os.listdir(d)
                  if n != "__init__.py" and n != "__pycache__"
                  and not n.endswith((".pyc", ".swp", "~")))
    assert live == sorted(V2_TEST_FILES), {
        "only_live": sorted(set(live) - set(V2_TEST_FILES)),
        "only_frozen": sorted(set(V2_TEST_FILES) - set(live))}

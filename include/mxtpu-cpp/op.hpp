/* GENERATED by tools/gen_cpp_op_wrappers.py — do not edit by hand.
 *
 * Typed Symbol-building wrappers for every registered operator, generated
 * from the op registry the same way the reference's OpWrapperGenerator.py
 * generates cpp-package/include/mxnet-cpp/op.h from its C API. Ops whose
 * signatures cannot be typed (var-keyword params) are reachable through
 * the generic Operator class instead.
 */
#ifndef MXTPU_CPP_OP_HPP_
#define MXTPU_CPP_OP_HPP_

#include <string>
#include <vector>

#include "base.hpp"
#include "operator.hpp"
#include "symbol.hpp"

namespace mxtpu {
namespace cpp {
namespace op {

inline Symbol Activation(const std::string &name,
    Symbol data,
    const std::string & act_type = "relu") {
  Operator op("Activation");
  if (act_type != "None") op.SetParam("act_type", act_type);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol BatchNorm(const std::string &name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    double eps = 0.001,
    double momentum = 0.9,
    bool fix_gamma = true,
    bool use_global_stats = false,
    bool output_mean_var = false,
    int axis = 1,
    bool cudnn_off = false) {
  Operator op("BatchNorm");
  op.SetParam("eps", NumStr(eps));
  op.SetParam("momentum", NumStr(momentum));
  op.SetParam("fix_gamma", (fix_gamma ? "true" : "false"));
  op.SetParam("use_global_stats", (use_global_stats ? "true" : "false"));
  op.SetParam("output_mean_var", (output_mean_var ? "true" : "false"));
  op.SetParam("axis", std::to_string(axis));
  op.SetParam("cudnn_off", (cudnn_off ? "true" : "false"));
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  op.SetInput("beta", beta);
  op.SetInput("moving_mean", moving_mean);
  op.SetInput("moving_var", moving_var);
  return op.CreateSymbol(name);
}

inline Symbol BilinearSampler(const std::string &name,
    Symbol data,
    Symbol grid) {
  Operator op("BilinearSampler");
  op.SetInput("data", data);
  op.SetInput("grid", grid);
  return op.CreateSymbol(name);
}

inline Symbol BlockGrad(const std::string &name,
    Symbol data) {
  Operator op("BlockGrad");
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol Convolution(const std::string &name,
    Symbol data,
    Symbol weight,
    Tuple kernel = Tuple{},
    Tuple stride = Tuple{},
    Tuple dilate = Tuple{},
    Tuple pad = Tuple{},
    int num_filter = 0,
    int num_group = 1,
    bool no_bias = false,
    int workspace = 1024,
    const std::string & cudnn_tune = "None",
    bool cudnn_off = false,
    const std::string & layout = "None",
    Symbol bias = Symbol()) {
  Operator op("Convolution");
  op.SetParam("kernel", TupleStr(kernel));
  op.SetParam("stride", TupleStr(stride));
  op.SetParam("dilate", TupleStr(dilate));
  op.SetParam("pad", TupleStr(pad));
  op.SetParam("num_filter", std::to_string(num_filter));
  op.SetParam("num_group", std::to_string(num_group));
  op.SetParam("no_bias", (no_bias ? "true" : "false"));
  op.SetParam("workspace", std::to_string(workspace));
  if (cudnn_tune != "None") op.SetParam("cudnn_tune", cudnn_tune);
  op.SetParam("cudnn_off", (cudnn_off ? "true" : "false"));
  if (layout != "None") op.SetParam("layout", layout);
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  if (!bias.IsNull()) op.SetInput("bias", bias);
  return op.CreateSymbol(name);
}

inline Symbol Correlation(const std::string &name,
    Symbol data1,
    Symbol data2,
    int kernel_size = 1,
    int max_displacement = 1,
    int stride1 = 1,
    int stride2 = 1,
    int pad_size = 0,
    bool is_multiply = true) {
  Operator op("Correlation");
  op.SetParam("kernel_size", std::to_string(kernel_size));
  op.SetParam("max_displacement", std::to_string(max_displacement));
  op.SetParam("stride1", std::to_string(stride1));
  op.SetParam("stride2", std::to_string(stride2));
  op.SetParam("pad_size", std::to_string(pad_size));
  op.SetParam("is_multiply", (is_multiply ? "true" : "false"));
  op.SetInput("data1", data1);
  op.SetInput("data2", data2);
  return op.CreateSymbol(name);
}

inline Symbol Crop(const std::string &name,
    Symbol data,
    Tuple offset = Tuple{0.0, 0.0},
    Tuple h_w = Tuple{0.0, 0.0},
    bool center_crop = false,
    int num_args = 1,
    Symbol crop_like = Symbol()) {
  Operator op("Crop");
  op.SetParam("offset", TupleStr(offset));
  op.SetParam("h_w", TupleStr(h_w));
  op.SetParam("center_crop", (center_crop ? "true" : "false"));
  op.SetParam("num_args", std::to_string(num_args));
  op.SetInput("data", data);
  if (!crop_like.IsNull()) op.SetInput("crop_like", crop_like);
  return op.CreateSymbol(name);
}

inline Symbol Custom(const std::string &name,
    const std::vector<Symbol> &inputs,
    const std::string & op_type = "None") {
  Operator op("Custom");
  if (op_type != "None") op.SetParam("op_type", op_type);
  for (const auto &s : inputs) op.PushInput(s);
  return op.CreateSymbol(name);
}

inline Symbol Deconvolution(const std::string &name,
    Symbol data,
    Symbol weight,
    Tuple kernel = Tuple{},
    Tuple stride = Tuple{},
    Tuple dilate = Tuple{},
    Tuple pad = Tuple{},
    Tuple adj = Tuple{},
    Tuple target_shape = Tuple{},
    int num_filter = 0,
    int num_group = 1,
    bool no_bias = true,
    int workspace = 512,
    const std::string & cudnn_tune = "None",
    bool cudnn_off = false,
    const std::string & layout = "None",
    Symbol bias = Symbol()) {
  Operator op("Deconvolution");
  op.SetParam("kernel", TupleStr(kernel));
  op.SetParam("stride", TupleStr(stride));
  op.SetParam("dilate", TupleStr(dilate));
  op.SetParam("pad", TupleStr(pad));
  op.SetParam("adj", TupleStr(adj));
  op.SetParam("target_shape", TupleStr(target_shape));
  op.SetParam("num_filter", std::to_string(num_filter));
  op.SetParam("num_group", std::to_string(num_group));
  op.SetParam("no_bias", (no_bias ? "true" : "false"));
  op.SetParam("workspace", std::to_string(workspace));
  if (cudnn_tune != "None") op.SetParam("cudnn_tune", cudnn_tune);
  op.SetParam("cudnn_off", (cudnn_off ? "true" : "false"));
  if (layout != "None") op.SetParam("layout", layout);
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  if (!bias.IsNull()) op.SetInput("bias", bias);
  return op.CreateSymbol(name);
}

inline Symbol Dropout(const std::string &name,
    Symbol data,
    double p = 0.5,
    const std::string & mode = "training",
    Tuple axes = Tuple{}) {
  Operator op("Dropout");
  op.SetParam("p", NumStr(p));
  if (mode != "None") op.SetParam("mode", mode);
  op.SetParam("axes", TupleStr(axes));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol Embedding(const std::string &name,
    Symbol data,
    Symbol weight,
    int input_dim = 0,
    int output_dim = 0,
    const std::string & dtype = "float32",
    bool sparse_grad = false) {
  Operator op("Embedding");
  op.SetParam("input_dim", std::to_string(input_dim));
  op.SetParam("output_dim", std::to_string(output_dim));
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetParam("sparse_grad", (sparse_grad ? "true" : "false"));
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  return op.CreateSymbol(name);
}

inline Symbol FullyConnected(const std::string &name,
    Symbol data,
    Symbol weight,
    int num_hidden = 0,
    bool no_bias = false,
    bool flatten = true,
    Symbol bias = Symbol()) {
  Operator op("FullyConnected");
  op.SetParam("num_hidden", std::to_string(num_hidden));
  op.SetParam("no_bias", (no_bias ? "true" : "false"));
  op.SetParam("flatten", (flatten ? "true" : "false"));
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  if (!bias.IsNull()) op.SetInput("bias", bias);
  return op.CreateSymbol(name);
}

inline Symbol GridGenerator(const std::string &name,
    Symbol data,
    const std::string & transform_type = "affine",
    Tuple target_shape = Tuple{0.0, 0.0}) {
  Operator op("GridGenerator");
  if (transform_type != "None") op.SetParam("transform_type", transform_type);
  op.SetParam("target_shape", TupleStr(target_shape));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol IdentityAttachKLSparseReg(const std::string &name,
    Symbol data,
    double sparseness_target = 0.1,
    double penalty = 0.001,
    double momentum = 0.9) {
  Operator op("IdentityAttachKLSparseReg");
  op.SetParam("sparseness_target", NumStr(sparseness_target));
  op.SetParam("penalty", NumStr(penalty));
  op.SetParam("momentum", NumStr(momentum));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol InstanceNorm(const std::string &name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    double eps = 0.001) {
  Operator op("InstanceNorm");
  op.SetParam("eps", NumStr(eps));
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  op.SetInput("beta", beta);
  return op.CreateSymbol(name);
}

inline Symbol L2Normalization(const std::string &name,
    Symbol data,
    double eps = 1e-10,
    const std::string & mode = "instance") {
  Operator op("L2Normalization");
  op.SetParam("eps", NumStr(eps));
  if (mode != "None") op.SetParam("mode", mode);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol LRN(const std::string &name,
    Symbol data,
    double alpha = 0.0001,
    double beta = 0.75,
    double knorm = 2.0,
    int nsize = 5) {
  Operator op("LRN");
  op.SetParam("alpha", NumStr(alpha));
  op.SetParam("beta", NumStr(beta));
  op.SetParam("knorm", NumStr(knorm));
  op.SetParam("nsize", std::to_string(nsize));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol LayerNorm(const std::string &name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    int axis = -1,
    double eps = 1e-05,
    bool output_mean_var = false) {
  Operator op("LayerNorm");
  op.SetParam("axis", std::to_string(axis));
  op.SetParam("eps", NumStr(eps));
  op.SetParam("output_mean_var", (output_mean_var ? "true" : "false"));
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  op.SetInput("beta", beta);
  return op.CreateSymbol(name);
}

inline Symbol LeakyReLU(const std::string &name,
    Symbol data,
    const std::string & act_type = "leaky",
    double slope = 0.25,
    double lower_bound = 0.125,
    double upper_bound = 0.334,
    Symbol gamma = Symbol()) {
  Operator op("LeakyReLU");
  if (act_type != "None") op.SetParam("act_type", act_type);
  op.SetParam("slope", NumStr(slope));
  op.SetParam("lower_bound", NumStr(lower_bound));
  op.SetParam("upper_bound", NumStr(upper_bound));
  op.SetInput("data", data);
  if (!gamma.IsNull()) op.SetInput("gamma", gamma);
  return op.CreateSymbol(name);
}

inline Symbol LinearRegressionOutput(const std::string &name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0) {
  Operator op("LinearRegressionOutput");
  op.SetParam("grad_scale", NumStr(grad_scale));
  op.SetInput("data", data);
  op.SetInput("label", label);
  return op.CreateSymbol(name);
}

inline Symbol LogisticRegressionOutput(const std::string &name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0) {
  Operator op("LogisticRegressionOutput");
  op.SetParam("grad_scale", NumStr(grad_scale));
  op.SetInput("data", data);
  op.SetInput("label", label);
  return op.CreateSymbol(name);
}

inline Symbol MAERegressionOutput(const std::string &name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0) {
  Operator op("MAERegressionOutput");
  op.SetParam("grad_scale", NumStr(grad_scale));
  op.SetInput("data", data);
  op.SetInput("label", label);
  return op.CreateSymbol(name);
}

inline Symbol MakeLoss(const std::string &name,
    Symbol data,
    double grad_scale = 1.0,
    double valid_thresh = 0.0,
    const std::string & normalization = "null") {
  Operator op("MakeLoss");
  op.SetParam("grad_scale", NumStr(grad_scale));
  op.SetParam("valid_thresh", NumStr(valid_thresh));
  if (normalization != "None") op.SetParam("normalization", normalization);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol Pooling(const std::string &name,
    Symbol data,
    Tuple kernel = Tuple{},
    const std::string & pool_type = "max",
    bool global_pool = false,
    Tuple stride = Tuple{},
    Tuple pad = Tuple{},
    const std::string & pooling_convention = "valid",
    bool cudnn_off = false,
    bool count_include_pad = true) {
  Operator op("Pooling");
  op.SetParam("kernel", TupleStr(kernel));
  if (pool_type != "None") op.SetParam("pool_type", pool_type);
  op.SetParam("global_pool", (global_pool ? "true" : "false"));
  op.SetParam("stride", TupleStr(stride));
  op.SetParam("pad", TupleStr(pad));
  if (pooling_convention != "None") op.SetParam("pooling_convention", pooling_convention);
  op.SetParam("cudnn_off", (cudnn_off ? "true" : "false"));
  op.SetParam("count_include_pad", (count_include_pad ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol RMSNorm(const std::string &name,
    Symbol data,
    Symbol gamma,
    int axis = -1,
    double eps = 1e-05) {
  Operator op("RMSNorm");
  op.SetParam("axis", std::to_string(axis));
  op.SetParam("eps", NumStr(eps));
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  return op.CreateSymbol(name);
}

inline Symbol RNN(const std::string &name,
    Symbol data,
    Symbol parameters,
    Symbol state,
    int state_size = 0,
    int num_layers = 1,
    bool bidirectional = false,
    const std::string & mode = "lstm",
    double p = 0.0,
    bool state_outputs = false,
    const std::string & lstm_state_clip_min = "None",
    const std::string & lstm_state_clip_max = "None",
    Symbol state_cell = Symbol()) {
  Operator op("RNN");
  op.SetParam("state_size", std::to_string(state_size));
  op.SetParam("num_layers", std::to_string(num_layers));
  op.SetParam("bidirectional", (bidirectional ? "true" : "false"));
  if (mode != "None") op.SetParam("mode", mode);
  op.SetParam("p", NumStr(p));
  op.SetParam("state_outputs", (state_outputs ? "true" : "false"));
  if (lstm_state_clip_min != "None") op.SetParam("lstm_state_clip_min", lstm_state_clip_min);
  if (lstm_state_clip_max != "None") op.SetParam("lstm_state_clip_max", lstm_state_clip_max);
  op.SetInput("data", data);
  op.SetInput("parameters", parameters);
  op.SetInput("state", state);
  if (!state_cell.IsNull()) op.SetInput("state_cell", state_cell);
  return op.CreateSymbol(name);
}

inline Symbol ROIPooling(const std::string &name,
    Symbol data,
    Symbol rois,
    Tuple pooled_size = Tuple{7.0, 7.0},
    double spatial_scale = 1.0) {
  Operator op("ROIPooling");
  op.SetParam("pooled_size", TupleStr(pooled_size));
  op.SetParam("spatial_scale", NumStr(spatial_scale));
  op.SetInput("data", data);
  op.SetInput("rois", rois);
  return op.CreateSymbol(name);
}

inline Symbol SVMOutput(const std::string &name,
    Symbol data,
    Symbol label,
    double margin = 1.0,
    double regularization_coefficient = 1.0,
    bool use_linear = false) {
  Operator op("SVMOutput");
  op.SetParam("margin", NumStr(margin));
  op.SetParam("regularization_coefficient", NumStr(regularization_coefficient));
  op.SetParam("use_linear", (use_linear ? "true" : "false"));
  op.SetInput("data", data);
  op.SetInput("label", label);
  return op.CreateSymbol(name);
}

inline Symbol SequenceLast(const std::string &name,
    Symbol data,
    bool use_sequence_length = false,
    int axis = 0,
    Symbol sequence_length = Symbol()) {
  Operator op("SequenceLast");
  op.SetParam("use_sequence_length", (use_sequence_length ? "true" : "false"));
  op.SetParam("axis", std::to_string(axis));
  op.SetInput("data", data);
  if (!sequence_length.IsNull()) op.SetInput("sequence_length", sequence_length);
  return op.CreateSymbol(name);
}

inline Symbol SequenceMask(const std::string &name,
    Symbol data,
    bool use_sequence_length = false,
    double value = 0.0,
    int axis = 0,
    Symbol sequence_length = Symbol()) {
  Operator op("SequenceMask");
  op.SetParam("use_sequence_length", (use_sequence_length ? "true" : "false"));
  op.SetParam("value", NumStr(value));
  op.SetParam("axis", std::to_string(axis));
  op.SetInput("data", data);
  if (!sequence_length.IsNull()) op.SetInput("sequence_length", sequence_length);
  return op.CreateSymbol(name);
}

inline Symbol SequenceReverse(const std::string &name,
    Symbol data,
    bool use_sequence_length = false,
    int axis = 0,
    Symbol sequence_length = Symbol()) {
  Operator op("SequenceReverse");
  op.SetParam("use_sequence_length", (use_sequence_length ? "true" : "false"));
  op.SetParam("axis", std::to_string(axis));
  op.SetInput("data", data);
  if (!sequence_length.IsNull()) op.SetInput("sequence_length", sequence_length);
  return op.CreateSymbol(name);
}

inline Symbol SoftmaxActivation(const std::string &name,
    Symbol data,
    const std::string & mode = "instance") {
  Operator op("SoftmaxActivation");
  if (mode != "None") op.SetParam("mode", mode);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol SoftmaxOutput(const std::string &name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0,
    double ignore_label = -1.0,
    bool multi_output = false,
    bool use_ignore = false,
    bool preserve_shape = false,
    const std::string & normalization = "null",
    bool out_grad = false,
    double smooth_alpha = 0.0) {
  Operator op("SoftmaxOutput");
  op.SetParam("grad_scale", NumStr(grad_scale));
  op.SetParam("ignore_label", NumStr(ignore_label));
  op.SetParam("multi_output", (multi_output ? "true" : "false"));
  op.SetParam("use_ignore", (use_ignore ? "true" : "false"));
  op.SetParam("preserve_shape", (preserve_shape ? "true" : "false"));
  if (normalization != "None") op.SetParam("normalization", normalization);
  op.SetParam("out_grad", (out_grad ? "true" : "false"));
  op.SetParam("smooth_alpha", NumStr(smooth_alpha));
  op.SetInput("data", data);
  op.SetInput("label", label);
  return op.CreateSymbol(name);
}

inline Symbol SpatialTransformer(const std::string &name,
    Symbol data,
    Symbol loc,
    Tuple target_shape = Tuple{0.0, 0.0},
    const std::string & transform_type = "affine",
    const std::string & sampler_type = "bilinear") {
  Operator op("SpatialTransformer");
  op.SetParam("target_shape", TupleStr(target_shape));
  if (transform_type != "None") op.SetParam("transform_type", transform_type);
  if (sampler_type != "None") op.SetParam("sampler_type", sampler_type);
  op.SetInput("data", data);
  op.SetInput("loc", loc);
  return op.CreateSymbol(name);
}

inline Symbol UpSampling(const std::string &name,
    const std::vector<Symbol> &args,
    int scale = 1,
    const std::string & sample_type = "nearest",
    int num_args = 1,
    int num_filter = 0,
    const std::string & multi_input_mode = "concat",
    int workspace = 512) {
  Operator op("UpSampling");
  op.SetParam("scale", std::to_string(scale));
  if (sample_type != "None") op.SetParam("sample_type", sample_type);
  op.SetParam("num_args", std::to_string(num_args));
  op.SetParam("num_filter", std::to_string(num_filter));
  if (multi_input_mode != "None") op.SetParam("multi_input_mode", multi_input_mode);
  op.SetParam("workspace", std::to_string(workspace));
  for (const auto &s : args) op.PushInput(s);
  return op.CreateSymbol(name);
}

inline Symbol contrib_DeformableConvolution(const std::string &name,
    Symbol data,
    Symbol offset,
    Symbol weight,
    Tuple kernel = Tuple{3.0, 3.0},
    Tuple stride = Tuple{1.0, 1.0},
    Tuple pad = Tuple{0.0, 0.0},
    Tuple dilate = Tuple{1.0, 1.0},
    int num_filter = 0,
    int num_group = 1,
    int num_deformable_group = 1,
    bool no_bias = false,
    int workspace = 1024,
    const std::string & layout = "None",
    Symbol bias = Symbol()) {
  Operator op("_contrib_DeformableConvolution");
  op.SetParam("kernel", TupleStr(kernel));
  op.SetParam("stride", TupleStr(stride));
  op.SetParam("pad", TupleStr(pad));
  op.SetParam("dilate", TupleStr(dilate));
  op.SetParam("num_filter", std::to_string(num_filter));
  op.SetParam("num_group", std::to_string(num_group));
  op.SetParam("num_deformable_group", std::to_string(num_deformable_group));
  op.SetParam("no_bias", (no_bias ? "true" : "false"));
  op.SetParam("workspace", std::to_string(workspace));
  if (layout != "None") op.SetParam("layout", layout);
  op.SetInput("data", data);
  op.SetInput("offset", offset);
  op.SetInput("weight", weight);
  if (!bias.IsNull()) op.SetInput("bias", bias);
  return op.CreateSymbol(name);
}

inline Symbol contrib_DeformablePSROIPooling(const std::string &name,
    Symbol data,
    Symbol rois,
    double spatial_scale = 1.0,
    int output_dim = 0,
    int group_size = 1,
    int pooled_size = 7,
    int part_size = 0,
    int sample_per_part = 4,
    double trans_std = 0.0,
    bool no_trans = false,
    Symbol trans = Symbol()) {
  Operator op("_contrib_DeformablePSROIPooling");
  op.SetParam("spatial_scale", NumStr(spatial_scale));
  op.SetParam("output_dim", std::to_string(output_dim));
  op.SetParam("group_size", std::to_string(group_size));
  op.SetParam("pooled_size", std::to_string(pooled_size));
  op.SetParam("part_size", std::to_string(part_size));
  op.SetParam("sample_per_part", std::to_string(sample_per_part));
  op.SetParam("trans_std", NumStr(trans_std));
  op.SetParam("no_trans", (no_trans ? "true" : "false"));
  op.SetInput("data", data);
  op.SetInput("rois", rois);
  if (!trans.IsNull()) op.SetInput("trans", trans);
  return op.CreateSymbol(name);
}

inline Symbol contrib_MultiBoxDetection(const std::string &name,
    Symbol cls_prob,
    Symbol loc_pred,
    Symbol anchor,
    bool clip = true,
    double threshold = 0.01,
    int background_id = 0,
    double nms_threshold = 0.5,
    bool force_suppress = false,
    Tuple variances = Tuple{0.1, 0.1, 0.2, 0.2},
    int nms_topk = -1) {
  Operator op("_contrib_MultiBoxDetection");
  op.SetParam("clip", (clip ? "true" : "false"));
  op.SetParam("threshold", NumStr(threshold));
  op.SetParam("background_id", std::to_string(background_id));
  op.SetParam("nms_threshold", NumStr(nms_threshold));
  op.SetParam("force_suppress", (force_suppress ? "true" : "false"));
  op.SetParam("variances", TupleStr(variances));
  op.SetParam("nms_topk", std::to_string(nms_topk));
  op.SetInput("cls_prob", cls_prob);
  op.SetInput("loc_pred", loc_pred);
  op.SetInput("anchor", anchor);
  return op.CreateSymbol(name);
}

inline Symbol contrib_MultiBoxPrior(const std::string &name,
    Symbol data,
    Tuple sizes = Tuple{1.0},
    Tuple ratios = Tuple{1.0},
    bool clip = false,
    Tuple steps = Tuple{-1.0, -1.0},
    Tuple offsets = Tuple{0.5, 0.5}) {
  Operator op("_contrib_MultiBoxPrior");
  op.SetParam("sizes", TupleStr(sizes));
  op.SetParam("ratios", TupleStr(ratios));
  op.SetParam("clip", (clip ? "true" : "false"));
  op.SetParam("steps", TupleStr(steps));
  op.SetParam("offsets", TupleStr(offsets));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol contrib_MultiBoxTarget(const std::string &name,
    Symbol anchor,
    Symbol label,
    Symbol cls_pred,
    double overlap_threshold = 0.5,
    double ignore_label = -1.0,
    double negative_mining_ratio = -1.0,
    double negative_mining_thresh = 0.5,
    Tuple variances = Tuple{0.1, 0.1, 0.2, 0.2}) {
  Operator op("_contrib_MultiBoxTarget");
  op.SetParam("overlap_threshold", NumStr(overlap_threshold));
  op.SetParam("ignore_label", NumStr(ignore_label));
  op.SetParam("negative_mining_ratio", NumStr(negative_mining_ratio));
  op.SetParam("negative_mining_thresh", NumStr(negative_mining_thresh));
  op.SetParam("variances", TupleStr(variances));
  op.SetInput("anchor", anchor);
  op.SetInput("label", label);
  op.SetInput("cls_pred", cls_pred);
  return op.CreateSymbol(name);
}

inline Symbol contrib_MultiProposal(const std::string &name,
    Symbol cls_prob,
    Symbol bbox_pred,
    Symbol im_info) {
  Operator op("_contrib_MultiProposal");
  op.SetInput("cls_prob", cls_prob);
  op.SetInput("bbox_pred", bbox_pred);
  op.SetInput("im_info", im_info);
  return op.CreateSymbol(name);
}

inline Symbol contrib_PSROIPooling(const std::string &name,
    Symbol data,
    Symbol rois,
    double spatial_scale = 1.0,
    int output_dim = 1,
    int pooled_size = 7,
    int group_size = 0) {
  Operator op("_contrib_PSROIPooling");
  op.SetParam("spatial_scale", NumStr(spatial_scale));
  op.SetParam("output_dim", std::to_string(output_dim));
  op.SetParam("pooled_size", std::to_string(pooled_size));
  op.SetParam("group_size", std::to_string(group_size));
  op.SetInput("data", data);
  op.SetInput("rois", rois);
  return op.CreateSymbol(name);
}

inline Symbol contrib_Proposal(const std::string &name,
    Symbol cls_prob,
    Symbol bbox_pred,
    Symbol im_info,
    int rpn_pre_nms_top_n = 6000,
    int rpn_post_nms_top_n = 300,
    double threshold = 0.7,
    int rpn_min_size = 16,
    Tuple scales = Tuple{4.0, 8.0, 16.0, 32.0},
    Tuple ratios = Tuple{0.5, 1.0, 2.0},
    int feature_stride = 16,
    bool output_score = false,
    bool iou_loss = false) {
  Operator op("_contrib_Proposal");
  op.SetParam("rpn_pre_nms_top_n", std::to_string(rpn_pre_nms_top_n));
  op.SetParam("rpn_post_nms_top_n", std::to_string(rpn_post_nms_top_n));
  op.SetParam("threshold", NumStr(threshold));
  op.SetParam("rpn_min_size", std::to_string(rpn_min_size));
  op.SetParam("scales", TupleStr(scales));
  op.SetParam("ratios", TupleStr(ratios));
  op.SetParam("feature_stride", std::to_string(feature_stride));
  op.SetParam("output_score", (output_score ? "true" : "false"));
  op.SetParam("iou_loss", (iou_loss ? "true" : "false"));
  op.SetInput("cls_prob", cls_prob);
  op.SetInput("bbox_pred", bbox_pred);
  op.SetInput("im_info", im_info);
  return op.CreateSymbol(name);
}

inline Symbol contrib_bipartite_matching(const std::string &name,
    Symbol data,
    double threshold = 0.5,
    bool is_ascend = false,
    int topk = -1) {
  Operator op("_contrib_bipartite_matching");
  op.SetParam("threshold", NumStr(threshold));
  op.SetParam("is_ascend", (is_ascend ? "true" : "false"));
  op.SetParam("topk", std::to_string(topk));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol contrib_box_iou(const std::string &name,
    Symbol lhs,
    Symbol rhs,
    const std::string & format = "corner") {
  Operator op("_contrib_box_iou");
  if (format != "None") op.SetParam("format", format);
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(name);
}

inline Symbol contrib_box_nms(const std::string &name,
    Symbol data,
    double overlap_thresh = 0.5,
    double valid_thresh = 0.0,
    int topk = -1,
    int coord_start = 2,
    int score_index = 1,
    int id_index = -1,
    bool force_suppress = false,
    const std::string & in_format = "corner",
    const std::string & out_format = "corner") {
  Operator op("_contrib_box_nms");
  op.SetParam("overlap_thresh", NumStr(overlap_thresh));
  op.SetParam("valid_thresh", NumStr(valid_thresh));
  op.SetParam("topk", std::to_string(topk));
  op.SetParam("coord_start", std::to_string(coord_start));
  op.SetParam("score_index", std::to_string(score_index));
  op.SetParam("id_index", std::to_string(id_index));
  op.SetParam("force_suppress", (force_suppress ? "true" : "false"));
  if (in_format != "None") op.SetParam("in_format", in_format);
  if (out_format != "None") op.SetParam("out_format", out_format);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol contrib_count_sketch(const std::string &name,
    Symbol data,
    Symbol h,
    Symbol s,
    int out_dim = 0,
    int processing_batch_size = 32) {
  Operator op("_contrib_count_sketch");
  op.SetParam("out_dim", std::to_string(out_dim));
  op.SetParam("processing_batch_size", std::to_string(processing_batch_size));
  op.SetInput("data", data);
  op.SetInput("h", h);
  op.SetInput("s", s);
  return op.CreateSymbol(name);
}

inline Symbol contrib_dequantize(const std::string &name,
    Symbol data,
    Symbol min_range,
    Symbol max_range,
    const std::string & out_type = "float32") {
  Operator op("_contrib_dequantize");
  if (out_type != "None") op.SetParam("out_type", out_type);
  op.SetInput("data", data);
  op.SetInput("min_range", min_range);
  op.SetInput("max_range", max_range);
  return op.CreateSymbol(name);
}

inline Symbol contrib_fft(const std::string &name,
    Symbol data,
    int compute_size = 128) {
  Operator op("_contrib_fft");
  op.SetParam("compute_size", std::to_string(compute_size));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol contrib_flash_attention(const std::string &name,
    Symbol q,
    Symbol k,
    Symbol v,
    bool causal = false,
    const std::string & scale = "None",
    int q_offset = 0,
    int k_offset = 0,
    const std::string & block_q = "None",
    const std::string & block_k = "None") {
  Operator op("_contrib_flash_attention");
  op.SetParam("causal", (causal ? "true" : "false"));
  if (scale != "None") op.SetParam("scale", scale);
  op.SetParam("q_offset", std::to_string(q_offset));
  op.SetParam("k_offset", std::to_string(k_offset));
  if (block_q != "None") op.SetParam("block_q", block_q);
  if (block_k != "None") op.SetParam("block_k", block_k);
  op.SetInput("q", q);
  op.SetInput("k", k);
  op.SetInput("v", v);
  return op.CreateSymbol(name);
}

inline Symbol contrib_gc_dequantize_2bit(const std::string &name,
    Symbol packed,
    double threshold = 0.5,
    const std::string & shape = "None") {
  Operator op("_contrib_gc_dequantize_2bit");
  op.SetParam("threshold", NumStr(threshold));
  if (shape != "None") op.SetParam("shape", shape);
  op.SetInput("packed", packed);
  return op.CreateSymbol(name);
}

inline Symbol contrib_gc_quantize_2bit(const std::string &name,
    Symbol data,
    Symbol residual,
    double threshold = 0.5) {
  Operator op("_contrib_gc_quantize_2bit");
  op.SetParam("threshold", NumStr(threshold));
  op.SetInput("data", data);
  op.SetInput("residual", residual);
  return op.CreateSymbol(name);
}

inline Symbol contrib_ifft(const std::string &name,
    Symbol data,
    int compute_size = 128) {
  Operator op("_contrib_ifft");
  op.SetParam("compute_size", std::to_string(compute_size));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol contrib_quadratic(const std::string &name,
    Symbol data,
    double a = 0.0,
    double b = 0.0,
    double c = 0.0) {
  Operator op("_contrib_quadratic");
  op.SetParam("a", NumStr(a));
  op.SetParam("b", NumStr(b));
  op.SetParam("c", NumStr(c));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol contrib_quantize(const std::string &name,
    Symbol data,
    Symbol min_range,
    Symbol max_range,
    const std::string & out_type = "uint8") {
  Operator op("_contrib_quantize");
  if (out_type != "None") op.SetParam("out_type", out_type);
  op.SetInput("data", data);
  op.SetInput("min_range", min_range);
  op.SetInput("max_range", max_range);
  return op.CreateSymbol(name);
}

inline Symbol div_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_div_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol equal_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_equal_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol greater_equal_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_greater_equal_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol greater_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_greater_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol hypot_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_hypot_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol image_normalize(const std::string &name,
    Symbol data,
    Tuple mean = Tuple{0.0},
    Tuple std = Tuple{1.0}) {
  Operator op("_image_normalize");
  op.SetParam("mean", TupleStr(mean));
  op.SetParam("std", TupleStr(std));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol image_to_tensor(const std::string &name,
    Symbol data) {
  Operator op("_image_to_tensor");
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol index(const std::string &name,
    Symbol data,
    Tuple key = Tuple{}) {
  Operator op("_index");
  op.SetParam("key", TupleStr(key));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol lesser_equal_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_lesser_equal_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol lesser_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_lesser_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol logical_and_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_logical_and_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol logical_or_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_logical_or_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol logical_xor_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_logical_xor_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol maximum_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_maximum_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol minimum_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_minimum_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol minus_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_minus_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol mod_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_mod_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol mul_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_mul_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol not_equal_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_not_equal_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol ones(const std::string &name,
    Tuple shape = Tuple{},
    const std::string & dtype = "float32") {
  Operator op("_ones");
  op.SetParam("shape", TupleStr(shape));
  if (dtype != "None") op.SetParam("dtype", dtype);
  return op.CreateSymbol(name);
}

inline Symbol plus_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_plus_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol power_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_power_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol rdiv_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_rdiv_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol rminus_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_rminus_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol rmod_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_rmod_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol rpower_scalar(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("_rpower_scalar");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol zeros(const std::string &name,
    Tuple shape = Tuple{},
    const std::string & dtype = "float32") {
  Operator op("_zeros");
  op.SetParam("shape", TupleStr(shape));
  if (dtype != "None") op.SetParam("dtype", dtype);
  return op.CreateSymbol(name);
}

inline Symbol abs(const std::string &name,
    Symbol x) {
  Operator op("abs");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol adagrad_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol history,
    const std::string & lr = "None",
    double epsilon = 1e-07,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  Operator op("adagrad_update");
  if (lr != "None") op.SetParam("lr", lr);
  op.SetParam("epsilon", NumStr(epsilon));
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("history", history);
  return op.CreateSymbol(name);
}

inline Symbol adam_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol mean,
    Symbol var,
    Symbol lr,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = true) {
  Operator op("adam_update");
  op.SetParam("beta1", NumStr(beta1));
  op.SetParam("beta2", NumStr(beta2));
  op.SetParam("epsilon", NumStr(epsilon));
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetParam("lazy_update", (lazy_update ? "true" : "false"));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("mean", mean);
  op.SetInput("var", var);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol add_n(const std::string &name,
    const std::vector<Symbol> &args) {
  Operator op("add_n");
  for (const auto &s : args) op.PushInput(s);
  return op.CreateSymbol(name);
}

inline Symbol arccos(const std::string &name,
    Symbol x) {
  Operator op("arccos");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol arccosh(const std::string &name,
    Symbol x) {
  Operator op("arccosh");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol arcsin(const std::string &name,
    Symbol x) {
  Operator op("arcsin");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol arcsinh(const std::string &name,
    Symbol x) {
  Operator op("arcsinh");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol arctan(const std::string &name,
    Symbol x) {
  Operator op("arctan");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol arctan2(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("arctan2");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol arctanh(const std::string &name,
    Symbol x) {
  Operator op("arctanh");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol argmax(const std::string &name,
    Symbol data,
    const std::string & axis = "None",
    bool keepdims = false) {
  Operator op("argmax");
  if (axis != "None") op.SetParam("axis", axis);
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol argmax_channel(const std::string &name,
    Symbol data) {
  Operator op("argmax_channel");
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol argmin(const std::string &name,
    Symbol data,
    const std::string & axis = "None",
    bool keepdims = false) {
  Operator op("argmin");
  if (axis != "None") op.SetParam("axis", axis);
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol argsort(const std::string &name,
    Symbol data,
    int axis = -1,
    bool is_ascend = true,
    const std::string & dtype = "float32") {
  Operator op("argsort");
  op.SetParam("axis", std::to_string(axis));
  op.SetParam("is_ascend", (is_ascend ? "true" : "false"));
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol batch_dot(const std::string &name,
    Symbol lhs,
    Symbol rhs,
    bool transpose_a = false,
    bool transpose_b = false) {
  Operator op("batch_dot");
  op.SetParam("transpose_a", (transpose_a ? "true" : "false"));
  op.SetParam("transpose_b", (transpose_b ? "true" : "false"));
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(name);
}

inline Symbol batch_take(const std::string &name,
    Symbol a,
    Symbol indices) {
  Operator op("batch_take");
  op.SetInput("a", a);
  op.SetInput("indices", indices);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_add(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_add");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_axis(const std::string &name,
    Symbol data,
    Tuple axis = Tuple{},
    Tuple size = Tuple{}) {
  Operator op("broadcast_axis");
  op.SetParam("axis", TupleStr(axis));
  op.SetParam("size", TupleStr(size));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_div(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_div");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_equal(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_equal");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_greater(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_greater");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_greater_equal(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_greater_equal");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_hypot(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_hypot");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_lesser(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_lesser");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_lesser_equal(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_lesser_equal");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_like(const std::string &name,
    Symbol data,
    Symbol like) {
  Operator op("broadcast_like");
  op.SetInput("data", data);
  op.SetInput("like", like);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_logical_and(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_logical_and");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_logical_or(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_logical_or");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_logical_xor(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_logical_xor");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_maximum(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_maximum");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_minimum(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_minimum");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_mod(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_mod");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_mul(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_mul");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_not_equal(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_not_equal");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_power(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_power");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_sub(const std::string &name,
    Symbol a,
    Symbol b) {
  Operator op("broadcast_sub");
  op.SetInput("a", a);
  op.SetInput("b", b);
  return op.CreateSymbol(name);
}

inline Symbol broadcast_to(const std::string &name,
    Symbol data,
    Tuple shape = Tuple{}) {
  Operator op("broadcast_to");
  op.SetParam("shape", TupleStr(shape));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol cached_attention(const std::string &name,
    Symbol query,
    Symbol key,
    Symbol value,
    Symbol k_cache,
    Symbol v_cache,
    Symbol pos,
    int num_heads = 1,
    bool alibi = false,
    int num_kv_heads = 0,
    int window = 0,
    double rope_theta = 0.0,
    double norm_eps = 1e-05,
    Symbol valid_len = Symbol(),
    Symbol q_gain = Symbol(),
    Symbol k_gain = Symbol()) {
  Operator op("cached_attention");
  op.SetParam("num_heads", std::to_string(num_heads));
  op.SetParam("alibi", (alibi ? "true" : "false"));
  op.SetParam("num_kv_heads", std::to_string(num_kv_heads));
  op.SetParam("window", std::to_string(window));
  op.SetParam("rope_theta", NumStr(rope_theta));
  op.SetParam("norm_eps", NumStr(norm_eps));
  op.SetInput("query", query);
  op.SetInput("key", key);
  op.SetInput("value", value);
  op.SetInput("k_cache", k_cache);
  op.SetInput("v_cache", v_cache);
  op.SetInput("pos", pos);
  if (!valid_len.IsNull()) op.SetInput("valid_len", valid_len);
  if (!q_gain.IsNull()) op.SetInput("q_gain", q_gain);
  if (!k_gain.IsNull()) op.SetInput("k_gain", k_gain);
  return op.CreateSymbol(name);
}

inline Symbol cast(const std::string &name,
    Symbol data,
    const std::string & dtype = "float32") {
  Operator op("cast");
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol cbrt(const std::string &name,
    Symbol x) {
  Operator op("cbrt");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol ceil(const std::string &name,
    Symbol x) {
  Operator op("ceil");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol clip(const std::string &name,
    Symbol data,
    const std::string & a_min = "None",
    const std::string & a_max = "None") {
  Operator op("clip");
  if (a_min != "None") op.SetParam("a_min", a_min);
  if (a_max != "None") op.SetParam("a_max", a_max);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol concat(const std::string &name,
    const std::vector<Symbol> &args,
    int dim = 1) {
  Operator op("concat");
  op.SetParam("dim", std::to_string(dim));
  for (const auto &s : args) op.PushInput(s);
  return op.CreateSymbol(name);
}

inline Symbol cos(const std::string &name,
    Symbol x) {
  Operator op("cos");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol cosh(const std::string &name,
    Symbol x) {
  Operator op("cosh");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol ctc_loss(const std::string &name,
    Symbol data,
    Symbol label,
    bool use_data_lengths = false,
    bool use_label_lengths = false,
    const std::string & blank_label = "first",
    Symbol data_lengths = Symbol(),
    Symbol label_lengths = Symbol()) {
  Operator op("ctc_loss");
  op.SetParam("use_data_lengths", (use_data_lengths ? "true" : "false"));
  op.SetParam("use_label_lengths", (use_label_lengths ? "true" : "false"));
  if (blank_label != "None") op.SetParam("blank_label", blank_label);
  op.SetInput("data", data);
  op.SetInput("label", label);
  if (!data_lengths.IsNull()) op.SetInput("data_lengths", data_lengths);
  if (!label_lengths.IsNull()) op.SetInput("label_lengths", label_lengths);
  return op.CreateSymbol(name);
}

inline Symbol degrees(const std::string &name,
    Symbol x) {
  Operator op("degrees");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol depth_to_space(const std::string &name,
    Symbol data,
    Symbol block_size) {
  Operator op("depth_to_space");
  op.SetInput("data", data);
  op.SetInput("block_size", block_size);
  return op.CreateSymbol(name);
}

inline Symbol diag(const std::string &name,
    Symbol data,
    int k = 0) {
  Operator op("diag");
  op.SetParam("k", std::to_string(k));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol dot(const std::string &name,
    Symbol lhs,
    Symbol rhs,
    bool transpose_a = false,
    bool transpose_b = false) {
  Operator op("dot");
  op.SetParam("transpose_a", (transpose_a ? "true" : "false"));
  op.SetParam("transpose_b", (transpose_b ? "true" : "false"));
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(name);
}

inline Symbol erf(const std::string &name,
    Symbol x) {
  Operator op("erf");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol exp(const std::string &name,
    Symbol x) {
  Operator op("exp");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol expand_dims(const std::string &name,
    Symbol data,
    int axis = 0) {
  Operator op("expand_dims");
  op.SetParam("axis", std::to_string(axis));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol expm1(const std::string &name,
    Symbol x) {
  Operator op("expm1");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol fix(const std::string &name,
    Symbol x) {
  Operator op("fix");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol flatten(const std::string &name,
    Symbol data) {
  Operator op("flatten");
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol floor(const std::string &name,
    Symbol x) {
  Operator op("floor");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol ftml_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol d,
    Symbol v,
    Symbol z,
    Symbol lr,
    int t = 1,
    double beta1 = 0.6,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_grad = -1.0) {
  Operator op("ftml_update");
  op.SetParam("t", std::to_string(t));
  op.SetParam("beta1", NumStr(beta1));
  op.SetParam("beta2", NumStr(beta2));
  op.SetParam("epsilon", NumStr(epsilon));
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_grad", NumStr(clip_grad));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("d", d);
  op.SetInput("v", v);
  op.SetInput("z", z);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol ftrl_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol z,
    Symbol n,
    Symbol lr,
    double lamda1 = 0.01,
    double beta = 1.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  Operator op("ftrl_update");
  op.SetParam("lamda1", NumStr(lamda1));
  op.SetParam("beta", NumStr(beta));
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("z", z);
  op.SetInput("n", n);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol gamma(const std::string &name,
    Symbol x) {
  Operator op("gamma");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol gammaln(const std::string &name,
    Symbol x) {
  Operator op("gammaln");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol gather_nd(const std::string &name,
    Symbol data,
    Symbol indices) {
  Operator op("gather_nd");
  op.SetInput("data", data);
  op.SetInput("indices", indices);
  return op.CreateSymbol(name);
}

inline Symbol hyper_merge(const std::string &name,
    Symbol carried,
    Symbol write,
    Symbol data) {
  Operator op("hyper_merge");
  op.SetInput("carried", carried);
  op.SetInput("write", write);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol hyper_mix(const std::string &name,
    Symbol streams,
    Symbol phi,
    Symbol alpha,
    Symbol base,
    int sinkhorn_iters = 20,
    double eps = 1e-06,
    double clamp_min = -30.0,
    double clamp_max = 30.0) {
  Operator op("hyper_mix");
  op.SetParam("sinkhorn_iters", std::to_string(sinkhorn_iters));
  op.SetParam("eps", NumStr(eps));
  op.SetParam("clamp_min", NumStr(clamp_min));
  op.SetParam("clamp_max", NumStr(clamp_max));
  op.SetInput("streams", streams);
  op.SetInput("phi", phi);
  op.SetInput("alpha", alpha);
  op.SetInput("base", base);
  return op.CreateSymbol(name);
}

inline Symbol identity(const std::string &name,
    Symbol data) {
  Operator op("identity");
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol khatri_rao(const std::string &name,
    const std::vector<Symbol> &args) {
  Operator op("khatri_rao");
  for (const auto &s : args) op.PushInput(s);
  return op.CreateSymbol(name);
}

inline Symbol latent_attention(const std::string &name,
    Symbol query,
    Symbol kv_row,
    Symbol c_gain,
    Symbol kv_up_weight,
    Symbol cache,
    Symbol pos,
    int num_heads = 1,
    int nope_dim = 128,
    int rope_dim = 64,
    int v_dim = 128,
    double scale = 0.0,
    double rope_theta = 10000.0,
    double rope_factor = 1.0,
    double rope_beta_fast = 32.0,
    double rope_beta_slow = 1.0,
    int rope_orig_len = 4096,
    double norm_eps = 1e-06,
    double pos_scale_beta = 0.0) {
  Operator op("latent_attention");
  op.SetParam("num_heads", std::to_string(num_heads));
  op.SetParam("nope_dim", std::to_string(nope_dim));
  op.SetParam("rope_dim", std::to_string(rope_dim));
  op.SetParam("v_dim", std::to_string(v_dim));
  op.SetParam("scale", NumStr(scale));
  op.SetParam("rope_theta", NumStr(rope_theta));
  op.SetParam("rope_factor", NumStr(rope_factor));
  op.SetParam("rope_beta_fast", NumStr(rope_beta_fast));
  op.SetParam("rope_beta_slow", NumStr(rope_beta_slow));
  op.SetParam("rope_orig_len", std::to_string(rope_orig_len));
  op.SetParam("norm_eps", NumStr(norm_eps));
  op.SetParam("pos_scale_beta", NumStr(pos_scale_beta));
  op.SetInput("query", query);
  op.SetInput("kv_row", kv_row);
  op.SetInput("c_gain", c_gain);
  op.SetInput("kv_up_weight", kv_up_weight);
  op.SetInput("cache", cache);
  op.SetInput("pos", pos);
  return op.CreateSymbol(name);
}

inline Symbol linalg_gelqf(const std::string &name,
    Symbol A) {
  Operator op("linalg_gelqf");
  op.SetInput("A", A);
  return op.CreateSymbol(name);
}

inline Symbol linalg_gemm(const std::string &name,
    Symbol A,
    Symbol B,
    Symbol C,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0,
    double beta = 1.0,
    int axis = -2) {
  Operator op("linalg_gemm");
  op.SetParam("transpose_a", (transpose_a ? "true" : "false"));
  op.SetParam("transpose_b", (transpose_b ? "true" : "false"));
  op.SetParam("alpha", NumStr(alpha));
  op.SetParam("beta", NumStr(beta));
  op.SetParam("axis", std::to_string(axis));
  op.SetInput("A", A);
  op.SetInput("B", B);
  op.SetInput("C", C);
  return op.CreateSymbol(name);
}

inline Symbol linalg_gemm2(const std::string &name,
    Symbol A,
    Symbol B,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0,
    int axis = -2) {
  Operator op("linalg_gemm2");
  op.SetParam("transpose_a", (transpose_a ? "true" : "false"));
  op.SetParam("transpose_b", (transpose_b ? "true" : "false"));
  op.SetParam("alpha", NumStr(alpha));
  op.SetParam("axis", std::to_string(axis));
  op.SetInput("A", A);
  op.SetInput("B", B);
  return op.CreateSymbol(name);
}

inline Symbol linalg_potrf(const std::string &name,
    Symbol A) {
  Operator op("linalg_potrf");
  op.SetInput("A", A);
  return op.CreateSymbol(name);
}

inline Symbol linalg_potri(const std::string &name,
    Symbol A) {
  Operator op("linalg_potri");
  op.SetInput("A", A);
  return op.CreateSymbol(name);
}

inline Symbol linalg_sumlogdiag(const std::string &name,
    Symbol A) {
  Operator op("linalg_sumlogdiag");
  op.SetInput("A", A);
  return op.CreateSymbol(name);
}

inline Symbol linalg_syevd(const std::string &name,
    Symbol A) {
  Operator op("linalg_syevd");
  op.SetInput("A", A);
  return op.CreateSymbol(name);
}

inline Symbol linalg_syrk(const std::string &name,
    Symbol A,
    bool transpose = false,
    double alpha = 1.0) {
  Operator op("linalg_syrk");
  op.SetParam("transpose", (transpose ? "true" : "false"));
  op.SetParam("alpha", NumStr(alpha));
  op.SetInput("A", A);
  return op.CreateSymbol(name);
}

inline Symbol linalg_trmm(const std::string &name,
    Symbol A,
    Symbol B,
    bool transpose = false,
    bool rightside = false,
    bool lower = true,
    double alpha = 1.0) {
  Operator op("linalg_trmm");
  op.SetParam("transpose", (transpose ? "true" : "false"));
  op.SetParam("rightside", (rightside ? "true" : "false"));
  op.SetParam("lower", (lower ? "true" : "false"));
  op.SetParam("alpha", NumStr(alpha));
  op.SetInput("A", A);
  op.SetInput("B", B);
  return op.CreateSymbol(name);
}

inline Symbol linalg_trsm(const std::string &name,
    Symbol A,
    Symbol B,
    bool transpose = false,
    bool rightside = false,
    bool lower = true,
    double alpha = 1.0) {
  Operator op("linalg_trsm");
  op.SetParam("transpose", (transpose ? "true" : "false"));
  op.SetParam("rightside", (rightside ? "true" : "false"));
  op.SetParam("lower", (lower ? "true" : "false"));
  op.SetParam("alpha", NumStr(alpha));
  op.SetInput("A", A);
  op.SetInput("B", B);
  return op.CreateSymbol(name);
}

inline Symbol log(const std::string &name,
    Symbol x) {
  Operator op("log");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol log10(const std::string &name,
    Symbol x) {
  Operator op("log10");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol log1p(const std::string &name,
    Symbol x) {
  Operator op("log1p");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol log2(const std::string &name,
    Symbol x) {
  Operator op("log2");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol log_softmax(const std::string &name,
    Symbol data,
    int axis = -1,
    const std::string & temperature = "None") {
  Operator op("log_softmax");
  op.SetParam("axis", std::to_string(axis));
  if (temperature != "None") op.SetParam("temperature", temperature);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol logical_not(const std::string &name,
    Symbol x) {
  Operator op("logical_not");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol max(const std::string &name,
    Symbol data,
    const std::string & axis = "None",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("max");
  if (axis != "None") op.SetParam("axis", axis);
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  op.SetParam("exclude", (exclude ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol mean(const std::string &name,
    Symbol data,
    const std::string & axis = "None",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("mean");
  if (axis != "None") op.SetParam("axis", axis);
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  op.SetParam("exclude", (exclude ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol min(const std::string &name,
    Symbol data,
    const std::string & axis = "None",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("min");
  if (axis != "None") op.SetParam("axis", axis);
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  op.SetParam("exclude", (exclude ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol moe_ffn(const std::string &name,
    Symbol data,
    Symbol gate_weight,
    Symbol w1,
    Symbol b1,
    Symbol w2,
    Symbol b2,
    double capacity_factor = 1.25,
    int num_selected = 1) {
  Operator op("moe_ffn");
  op.SetParam("capacity_factor", NumStr(capacity_factor));
  op.SetParam("num_selected", std::to_string(num_selected));
  op.SetInput("data", data);
  op.SetInput("gate_weight", gate_weight);
  op.SetInput("w1", w1);
  op.SetInput("b1", b1);
  op.SetInput("w2", w2);
  op.SetInput("b2", b2);
  return op.CreateSymbol(name);
}

inline Symbol moe_ffn_held(const std::string &name,
    Symbol data,
    Symbol router_weight,
    Symbol select_bias,
    Symbol gate_weight,
    Symbol up_weight,
    Symbol down_weight,
    int top_k = 1,
    int expert_first = 0,
    double scale = 1.0,
    const std::string & scoring = "sigmoid",
    Symbol load = Symbol(),
    Symbol valid_len = Symbol()) {
  Operator op("moe_ffn_held");
  op.SetParam("top_k", std::to_string(top_k));
  op.SetParam("expert_first", std::to_string(expert_first));
  op.SetParam("scale", NumStr(scale));
  if (scoring != "None") op.SetParam("scoring", scoring);
  op.SetInput("data", data);
  op.SetInput("router_weight", router_weight);
  op.SetInput("select_bias", select_bias);
  op.SetInput("gate_weight", gate_weight);
  op.SetInput("up_weight", up_weight);
  op.SetInput("down_weight", down_weight);
  if (!load.IsNull()) op.SetInput("load", load);
  if (!valid_len.IsNull()) op.SetInput("valid_len", valid_len);
  return op.CreateSymbol(name);
}

inline Symbol mp_sgd_mom_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    Symbol weight32,
    Symbol lr,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  Operator op("mp_sgd_mom_update");
  op.SetParam("momentum", NumStr(momentum));
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("mom", mom);
  op.SetInput("weight32", weight32);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol mp_sgd_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol weight32,
    Symbol lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  Operator op("mp_sgd_update");
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("weight32", weight32);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol nanprod(const std::string &name,
    Symbol data,
    const std::string & axis = "None",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("nanprod");
  if (axis != "None") op.SetParam("axis", axis);
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  op.SetParam("exclude", (exclude ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol nansum(const std::string &name,
    Symbol data,
    const std::string & axis = "None",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("nansum");
  if (axis != "None") op.SetParam("axis", axis);
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  op.SetParam("exclude", (exclude ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol negative(const std::string &name,
    const std::vector<Symbol> &args,
    const std::string & out = "None",
    const std::string & where = "None") {
  Operator op("negative");
  if (out != "None") op.SetParam("out", out);
  if (where != "None") op.SetParam("where", where);
  for (const auto &s : args) op.PushInput(s);
  return op.CreateSymbol(name);
}

inline Symbol norm(const std::string &name,
    Symbol data,
    int ord = 2,
    const std::string & axis = "None",
    bool keepdims = false) {
  Operator op("norm");
  op.SetParam("ord", std::to_string(ord));
  if (axis != "None") op.SetParam("axis", axis);
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol one_hot(const std::string &name,
    Symbol indices,
    int depth = 0,
    double on_value = 1.0,
    double off_value = 0.0,
    const std::string & dtype = "float32") {
  Operator op("one_hot");
  op.SetParam("depth", std::to_string(depth));
  op.SetParam("on_value", NumStr(on_value));
  op.SetParam("off_value", NumStr(off_value));
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("indices", indices);
  return op.CreateSymbol(name);
}

inline Symbol ones_like(const std::string &name,
    Symbol data) {
  Operator op("ones_like");
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol pad(const std::string &name,
    Symbol data,
    const std::string & mode = "constant",
    Tuple pad_width = Tuple{},
    double constant_value = 0.0) {
  Operator op("pad");
  if (mode != "None") op.SetParam("mode", mode);
  op.SetParam("pad_width", TupleStr(pad_width));
  op.SetParam("constant_value", NumStr(constant_value));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol pick(const std::string &name,
    Symbol data,
    Symbol index,
    int axis = -1,
    bool keepdims = false,
    const std::string & mode = "clip") {
  Operator op("pick");
  op.SetParam("axis", std::to_string(axis));
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  if (mode != "None") op.SetParam("mode", mode);
  op.SetInput("data", data);
  op.SetInput("index", index);
  return op.CreateSymbol(name);
}

inline Symbol prod(const std::string &name,
    Symbol data,
    const std::string & axis = "None",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("prod");
  if (axis != "None") op.SetParam("axis", axis);
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  op.SetParam("exclude", (exclude ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol radians(const std::string &name,
    Symbol x) {
  Operator op("radians");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol random_exponential(const std::string &name,
    double lam = 1.0,
    const std::string & shape = "None",
    const std::string & dtype = "float32") {
  Operator op("random_exponential");
  op.SetParam("lam", NumStr(lam));
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  return op.CreateSymbol(name);
}

inline Symbol random_gamma(const std::string &name,
    double alpha = 1.0,
    double beta = 1.0,
    const std::string & shape = "None",
    const std::string & dtype = "float32") {
  Operator op("random_gamma");
  op.SetParam("alpha", NumStr(alpha));
  op.SetParam("beta", NumStr(beta));
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  return op.CreateSymbol(name);
}

inline Symbol random_generalized_negative_binomial(const std::string &name,
    double mu = 1.0,
    double alpha = 1.0,
    const std::string & shape = "None",
    const std::string & dtype = "float32") {
  Operator op("random_generalized_negative_binomial");
  op.SetParam("mu", NumStr(mu));
  op.SetParam("alpha", NumStr(alpha));
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  return op.CreateSymbol(name);
}

inline Symbol random_negative_binomial(const std::string &name,
    int k = 1,
    double p = 1.0,
    const std::string & shape = "None",
    const std::string & dtype = "float32") {
  Operator op("random_negative_binomial");
  op.SetParam("k", std::to_string(k));
  op.SetParam("p", NumStr(p));
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  return op.CreateSymbol(name);
}

inline Symbol random_normal(const std::string &name,
    double loc = 0.0,
    double scale = 1.0,
    const std::string & shape = "None",
    const std::string & dtype = "float32") {
  Operator op("random_normal");
  op.SetParam("loc", NumStr(loc));
  op.SetParam("scale", NumStr(scale));
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  return op.CreateSymbol(name);
}

inline Symbol random_poisson(const std::string &name,
    double lam = 1.0,
    const std::string & shape = "None",
    const std::string & dtype = "float32") {
  Operator op("random_poisson");
  op.SetParam("lam", NumStr(lam));
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  return op.CreateSymbol(name);
}

inline Symbol random_randint(const std::string &name,
    int low = 0,
    int high = 1,
    const std::string & shape = "None",
    const std::string & dtype = "int32") {
  Operator op("random_randint");
  op.SetParam("low", std::to_string(low));
  op.SetParam("high", std::to_string(high));
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  return op.CreateSymbol(name);
}

inline Symbol random_uniform(const std::string &name,
    double low = 0.0,
    double high = 1.0,
    const std::string & shape = "None",
    const std::string & dtype = "float32") {
  Operator op("random_uniform");
  op.SetParam("low", NumStr(low));
  op.SetParam("high", NumStr(high));
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  return op.CreateSymbol(name);
}

inline Symbol rcbrt(const std::string &name,
    Symbol x) {
  Operator op("rcbrt");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol reciprocal(const std::string &name,
    Symbol x) {
  Operator op("reciprocal");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol relu(const std::string &name,
    Symbol x) {
  Operator op("relu");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol repeat(const std::string &name,
    Symbol data,
    int repeats = 1,
    const std::string & axis = "None") {
  Operator op("repeat");
  op.SetParam("repeats", std::to_string(repeats));
  if (axis != "None") op.SetParam("axis", axis);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol reshape(const std::string &name,
    Symbol data,
    const std::string & shape = "None",
    bool reverse = false) {
  Operator op("reshape");
  if (shape != "None") op.SetParam("shape", shape);
  op.SetParam("reverse", (reverse ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol reshape_like(const std::string &name,
    Symbol lhs,
    Symbol rhs) {
  Operator op("reshape_like");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(name);
}

inline Symbol reverse(const std::string &name,
    Symbol data,
    int axis = 0) {
  Operator op("reverse");
  op.SetParam("axis", std::to_string(axis));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol rint(const std::string &name,
    Symbol x) {
  Operator op("rint");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol rmsprop_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol n,
    Symbol lr,
    double gamma1 = 0.95,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double clip_weights = -1.0) {
  Operator op("rmsprop_update");
  op.SetParam("gamma1", NumStr(gamma1));
  op.SetParam("epsilon", NumStr(epsilon));
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetParam("clip_weights", NumStr(clip_weights));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("n", n);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol rmspropalex_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol n,
    Symbol g_state,
    Symbol delta,
    Symbol lr,
    double gamma1 = 0.95,
    double gamma2 = 0.9,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double clip_weights = -1.0) {
  Operator op("rmspropalex_update");
  op.SetParam("gamma1", NumStr(gamma1));
  op.SetParam("gamma2", NumStr(gamma2));
  op.SetParam("epsilon", NumStr(epsilon));
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetParam("clip_weights", NumStr(clip_weights));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("n", n);
  op.SetInput("g_state", g_state);
  op.SetInput("delta", delta);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol round(const std::string &name,
    Symbol a,
    int decimals = 0,
    const std::string & out = "None") {
  Operator op("round");
  op.SetParam("decimals", std::to_string(decimals));
  if (out != "None") op.SetParam("out", out);
  op.SetInput("a", a);
  return op.CreateSymbol(name);
}

inline Symbol rsqrt(const std::string &name,
    Symbol x) {
  Operator op("rsqrt");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol sample_exponential(const std::string &name,
    Symbol lam,
    const std::string & shape = "None",
    const std::string & dtype = "None") {
  Operator op("sample_exponential");
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("lam", lam);
  return op.CreateSymbol(name);
}

inline Symbol sample_gamma(const std::string &name,
    Symbol alpha,
    Symbol beta,
    const std::string & shape = "None",
    const std::string & dtype = "None") {
  Operator op("sample_gamma");
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("alpha", alpha);
  op.SetInput("beta", beta);
  return op.CreateSymbol(name);
}

inline Symbol sample_generalized_negative_binomial(const std::string &name,
    Symbol mu,
    Symbol alpha,
    const std::string & shape = "None",
    const std::string & dtype = "float32") {
  Operator op("sample_generalized_negative_binomial");
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("mu", mu);
  op.SetInput("alpha", alpha);
  return op.CreateSymbol(name);
}

inline Symbol sample_multinomial(const std::string &name,
    Symbol data,
    const std::string & shape = "None",
    bool get_prob = false,
    const std::string & dtype = "int32") {
  Operator op("sample_multinomial");
  if (shape != "None") op.SetParam("shape", shape);
  op.SetParam("get_prob", (get_prob ? "true" : "false"));
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol sample_negative_binomial(const std::string &name,
    Symbol k,
    Symbol p,
    const std::string & shape = "None",
    const std::string & dtype = "float32") {
  Operator op("sample_negative_binomial");
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("k", k);
  op.SetInput("p", p);
  return op.CreateSymbol(name);
}

inline Symbol sample_normal(const std::string &name,
    Symbol mu,
    Symbol sigma,
    const std::string & shape = "None",
    const std::string & dtype = "None") {
  Operator op("sample_normal");
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("mu", mu);
  op.SetInput("sigma", sigma);
  return op.CreateSymbol(name);
}

inline Symbol sample_poisson(const std::string &name,
    Symbol lam,
    const std::string & shape = "None",
    const std::string & dtype = "float32") {
  Operator op("sample_poisson");
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("lam", lam);
  return op.CreateSymbol(name);
}

inline Symbol sample_uniform(const std::string &name,
    Symbol low,
    Symbol high,
    const std::string & shape = "None",
    const std::string & dtype = "None") {
  Operator op("sample_uniform");
  if (shape != "None") op.SetParam("shape", shape);
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("low", low);
  op.SetInput("high", high);
  return op.CreateSymbol(name);
}

inline Symbol scatter_nd(const std::string &name,
    Symbol data,
    Symbol indices,
    Tuple shape = Tuple{}) {
  Operator op("scatter_nd");
  op.SetParam("shape", TupleStr(shape));
  op.SetInput("data", data);
  op.SetInput("indices", indices);
  return op.CreateSymbol(name);
}

inline Symbol sgd_mom_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    Symbol lr,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = true) {
  Operator op("sgd_mom_update");
  op.SetParam("momentum", NumStr(momentum));
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetParam("lazy_update", (lazy_update ? "true" : "false"));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("mom", mom);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol sgd_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = true) {
  Operator op("sgd_update");
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetParam("lazy_update", (lazy_update ? "true" : "false"));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol shape_array(const std::string &name,
    Symbol data) {
  Operator op("shape_array");
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol shuffle(const std::string &name,
    Symbol data) {
  Operator op("shuffle");
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol sigmoid(const std::string &name,
    Symbol x) {
  Operator op("sigmoid");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol sign(const std::string &name,
    Symbol x) {
  Operator op("sign");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol signsgd_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  Operator op("signsgd_update");
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol signum_update(const std::string &name,
    Symbol weight,
    Symbol grad,
    Symbol mom,
    Symbol lr,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double wd_lh = 0.0) {
  Operator op("signum_update");
  op.SetParam("momentum", NumStr(momentum));
  op.SetParam("wd", NumStr(wd));
  op.SetParam("rescale_grad", NumStr(rescale_grad));
  op.SetParam("clip_gradient", NumStr(clip_gradient));
  op.SetParam("wd_lh", NumStr(wd_lh));
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("mom", mom);
  op.SetInput("lr", lr);
  return op.CreateSymbol(name);
}

inline Symbol sin(const std::string &name,
    Symbol x) {
  Operator op("sin");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol sinh(const std::string &name,
    Symbol x) {
  Operator op("sinh");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol size_array(const std::string &name,
    Symbol data) {
  Operator op("size_array");
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol slice(const std::string &name,
    Symbol data,
    Tuple begin = Tuple{},
    Tuple end = Tuple{},
    Tuple step = Tuple{}) {
  Operator op("slice");
  op.SetParam("begin", TupleStr(begin));
  op.SetParam("end", TupleStr(end));
  op.SetParam("step", TupleStr(step));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol slice_axis(const std::string &name,
    Symbol data,
    int axis = 0,
    int begin = 0,
    const std::string & end = "None") {
  Operator op("slice_axis");
  op.SetParam("axis", std::to_string(axis));
  op.SetParam("begin", std::to_string(begin));
  if (end != "None") op.SetParam("end", end);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol slice_like(const std::string &name,
    Symbol data,
    Symbol shape_like,
    Tuple axes = Tuple{}) {
  Operator op("slice_like");
  op.SetParam("axes", TupleStr(axes));
  op.SetInput("data", data);
  op.SetInput("shape_like", shape_like);
  return op.CreateSymbol(name);
}

inline Symbol smooth_l1(const std::string &name,
    Symbol data,
    double scalar = 1.0) {
  Operator op("smooth_l1");
  op.SetParam("scalar", NumStr(scalar));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol softmax(const std::string &name,
    Symbol data,
    int axis = -1,
    const std::string & temperature = "None") {
  Operator op("softmax");
  op.SetParam("axis", std::to_string(axis));
  if (temperature != "None") op.SetParam("temperature", temperature);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol softmax_cross_entropy(const std::string &name,
    Symbol data,
    Symbol label) {
  Operator op("softmax_cross_entropy");
  op.SetInput("data", data);
  op.SetInput("label", label);
  return op.CreateSymbol(name);
}

inline Symbol softrelu(const std::string &name,
    Symbol x) {
  Operator op("softrelu");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol softsign(const std::string &name,
    Symbol x) {
  Operator op("softsign");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol sort(const std::string &name,
    Symbol data,
    int axis = -1,
    bool is_ascend = true) {
  Operator op("sort");
  op.SetParam("axis", std::to_string(axis));
  op.SetParam("is_ascend", (is_ascend ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol space_to_depth(const std::string &name,
    Symbol data,
    Symbol block_size) {
  Operator op("space_to_depth");
  op.SetInput("data", data);
  op.SetInput("block_size", block_size);
  return op.CreateSymbol(name);
}

inline Symbol split(const std::string &name,
    Symbol data,
    int num_outputs = 2,
    int axis = 1,
    bool squeeze_axis = false) {
  Operator op("split");
  op.SetParam("num_outputs", std::to_string(num_outputs));
  op.SetParam("axis", std::to_string(axis));
  op.SetParam("squeeze_axis", (squeeze_axis ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol sqrt(const std::string &name,
    Symbol x) {
  Operator op("sqrt");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol square(const std::string &name,
    Symbol x) {
  Operator op("square");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol squeeze(const std::string &name,
    Symbol data,
    const std::string & axis = "None") {
  Operator op("squeeze");
  if (axis != "None") op.SetParam("axis", axis);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol stack(const std::string &name,
    const std::vector<Symbol> &args,
    int axis = 0) {
  Operator op("stack");
  op.SetParam("axis", std::to_string(axis));
  for (const auto &s : args) op.PushInput(s);
  return op.CreateSymbol(name);
}

inline Symbol sum(const std::string &name,
    Symbol data,
    const std::string & axis = "None",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("sum");
  if (axis != "None") op.SetParam("axis", axis);
  op.SetParam("keepdims", (keepdims ? "true" : "false"));
  op.SetParam("exclude", (exclude ? "true" : "false"));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol swapaxes(const std::string &name,
    Symbol data,
    int dim1 = 0,
    int dim2 = 0) {
  Operator op("swapaxes");
  op.SetParam("dim1", std::to_string(dim1));
  op.SetParam("dim2", std::to_string(dim2));
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol take(const std::string &name,
    Symbol a,
    Symbol indices,
    int axis = 0,
    const std::string & mode = "clip") {
  Operator op("take");
  op.SetParam("axis", std::to_string(axis));
  if (mode != "None") op.SetParam("mode", mode);
  op.SetInput("a", a);
  op.SetInput("indices", indices);
  return op.CreateSymbol(name);
}

inline Symbol tan(const std::string &name,
    Symbol x) {
  Operator op("tan");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol tanh(const std::string &name,
    Symbol x) {
  Operator op("tanh");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol tile(const std::string &name,
    Symbol data,
    Symbol reps) {
  Operator op("tile");
  op.SetInput("data", data);
  op.SetInput("reps", reps);
  return op.CreateSymbol(name);
}

inline Symbol topk(const std::string &name,
    Symbol data,
    int axis = -1,
    int k = 1,
    const std::string & ret_typ = "indices",
    bool is_ascend = false,
    const std::string & dtype = "float32") {
  Operator op("topk");
  op.SetParam("axis", std::to_string(axis));
  op.SetParam("k", std::to_string(k));
  if (ret_typ != "None") op.SetParam("ret_typ", ret_typ);
  op.SetParam("is_ascend", (is_ascend ? "true" : "false"));
  if (dtype != "None") op.SetParam("dtype", dtype);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol transpose(const std::string &name,
    Symbol data,
    const std::string & axes = "None") {
  Operator op("transpose");
  if (axes != "None") op.SetParam("axes", axes);
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

inline Symbol trunc(const std::string &name,
    Symbol x) {
  Operator op("trunc");
  op.SetInput("x", x);
  return op.CreateSymbol(name);
}

inline Symbol where(const std::string &name,
    Symbol condition,
    Symbol x,
    Symbol y) {
  Operator op("where");
  op.SetInput("condition", condition);
  op.SetInput("x", x);
  op.SetInput("y", y);
  return op.CreateSymbol(name);
}

inline Symbol zeros_like(const std::string &name,
    Symbol data) {
  Operator op("zeros_like");
  op.SetInput("data", data);
  return op.CreateSymbol(name);
}

}  // namespace op
}  // namespace cpp
}  // namespace mxtpu

#endif  // MXTPU_CPP_OP_HPP_
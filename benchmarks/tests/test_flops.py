"""The FLOP functions against hand counts."""

import flops
import run_cell


def _ref(name):
    return run_cell.load_module(run_cell.HERE / "configs" / f"{name}.py")


def test_vgg16_first_conv_layer_by_hand():
    # 32x32 outputs, 64 filters, each a 3x3x3 dot product: 27 multiply-adds
    assert flops.conv2d(32, 32, 3, 64, 3) == 2 * 32 * 32 * 64 * 27 == 3538944


def test_bert_base_block_by_hand():
    s, d, f = 128, 768, 3072
    qkvo = 4 * 2 * s * d * d            # four d x d projections
    scores_context = 2 * 2 * s * s * d  # QK^T and PV over all 12 heads
    ffn = 2 * 2 * s * d * f
    assert flops.transformer_block(s, d, 12, f) \
        == qkvo + scores_context + ffn == 1862270976


def test_vgg16_whole_model():
    # 13 convs and 3 dense layers; forward multiply-adds x 2 x 3
    convs = [(32, 3, 64), (32, 64, 64), (16, 64, 128), (16, 128, 128),
             (8, 128, 256), (8, 256, 256), (8, 256, 256), (4, 256, 512),
             (4, 512, 512), (4, 512, 512), (2, 512, 512), (2, 512, 512),
             (2, 512, 512)]
    fwd = sum(2 * h * h * cin * cout * 9 for h, cin, cout in convs) \
        + 2 * (512 * 4096 + 4096 * 4096 + 4096 * 10)
    assert _ref("vgg16_c7").train_flops_per_sample(flops) == 3 * fwd


def test_bert_base_whole_model_near_six_times_parameters_times_tokens():
    got = _ref("bert_base_c7").train_flops_per_sample(flops)
    block_params = 12 * (4 * 768 * 768 + 2 * 768 * 3072)
    assert 1.0 < got / (6 * block_params * 128) < 1.06  # + attention

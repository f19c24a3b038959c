"""The model-FLOPs arithmetic, stated once more outside the benchmark.

This is no benchmark and runs nothing: the repository's one benchmark is
``benchmark/run.py`` (``BENCHMARK.json``). What is left here is the
independent statement of the model FLOPs of a BERT token and a ResNet-50
image that ``tests/benchmark/test_benchmark_files.py::TestFlops`` holds the
copies in ``benchmark/configs/*.py`` against. A ``benchmark`` PR may
repoint that test and remove this file.
"""

# Analytic ResNet-50 cost: ~4.09 GMACs forward at 224x224 (8.18 GFLOPs);
# training ~= 3x forward (backward is ~2x).
RESNET50_TRAIN_FLOPS_PER_IMAGE_224 = 3 * 2 * 4.089e9


# BERT-Large analytic FLOPs/token (fwd), masked-position head:
#   layers: 2 * L * (4H^2 + 2HI); attention: 4 * L * S * H;
#   head (transform + tied logits) scaled by P/S. Train = 3x fwd.
def bert_flops_per_token(cfg, seq_len: int, num_predictions: int) -> float:
    H, I, L, V = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                  cfg.vocab_size)
    layer_matmuls = 2.0 * L * (4 * H * H + 2 * H * I)
    attention = 4.0 * L * seq_len * H
    head = 2.0 * (H * H + V * H) * (num_predictions / seq_len)
    return 3.0 * (layer_matmuls + attention + head)

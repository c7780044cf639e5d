"""The paper's 12 simulation workloads (Table 1) as DNNGs.

Two groups (§4.1): *heavy* multi-domain (AlexNet, ResNet-50, GoogLeNet,
SA_CNN, SA_LSTM, NCF, AlphaGoZero, Transformer) and *light* RNN
(Melody-LSTM, Google-Translate/GNMT, DeepVoice, Handwriting-LSTM).

The paper does not publish per-layer dimensions, so layers use the standard
published configurations of each model (original papers / torchvision), at
inference batch 1.  LSTMs lower to one GEMM per layer with the 4 gates fused
(M = 4·hidden, K = input+hidden) and time steps folded into the streamed
dimension — the same lowering Scale-Sim's topology files use.

Calibration notes (EXPERIMENTS.md §Fig9):

* Sequence lengths are **inference-request scale** (the paper's INFaaS
  multi-tenant serving context): one 1 s melody chunk (100 × 10 ms frames),
  one 0.1 s vocoder chunk (1600 samples), one 200-point pen stroke, one
  20-token sentence.  The paper does not publish these; magnitudes of the
  reported savings are sensitive to them (longer sequences raise useful-MAC
  density and shrink the baseline's idle-multiplier waste that the Mul_En
  PE eliminates).
* Arrivals are staggered inside the first layer's execution window exactly
  as the paper's Fig. 4 timeline shows (A_t1..A_tn ≤ A_t0 + τ0), so the
  first DNNG's first layer runs on the whole array (Fig. 5 line 5).
"""

from __future__ import annotations

from repro.core.dnng import DNNG, LayerShape, chain
from repro.sim import mellum

Conv = LayerShape.conv
FC = LayerShape.fc
LSTM = LayerShape.lstm_cell


# ---------------------------------------------------------------------------
# Heavy multi-domain workload
# ---------------------------------------------------------------------------

def alexnet() -> DNNG:
    return chain("AlexNet", [
        Conv("conv1", M=96, C=3, R=11, S=11, H=227, W=227, stride=4, pad=0),
        Conv("conv2", M=256, C=96, R=5, S=5, H=27, W=27, pad=2),
        Conv("conv3", M=384, C=256, R=3, S=3, H=13, W=13),
        Conv("conv4", M=384, C=384, R=3, S=3, H=13, W=13),
        Conv("conv5", M=256, C=384, R=3, S=3, H=13, W=13),
        FC("fc6", 9216, 4096),
        FC("fc7", 4096, 4096),
        FC("fc8", 4096, 1000),
    ])


def resnet50() -> DNNG:
    layers: list[LayerShape] = [
        Conv("conv1", M=64, C=3, R=7, S=7, H=224, W=224, stride=2, pad=3)]
    spatial = 56
    in_ch = 64
    stage_cfg = [  # (n_blocks, mid_channels, out_channels, first_stride)
        (3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2), (3, 512, 2048, 2)]
    for s, (blocks, mid, out, stride0) in enumerate(stage_cfg):
        for b in range(blocks):
            stride = stride0 if b == 0 else 1
            h = spatial
            layers.append(Conv(f"s{s}b{b}_1x1a", M=mid, C=in_ch, R=1, S=1,
                               H=h, W=h, stride=stride, pad=0))
            h2 = h // stride
            layers.append(Conv(f"s{s}b{b}_3x3", M=mid, C=mid, R=3, S=3,
                               H=h2, W=h2))
            layers.append(Conv(f"s{s}b{b}_1x1b", M=out, C=mid, R=1, S=1,
                               H=h2, W=h2, pad=0))
            if b == 0:
                layers.append(Conv(f"s{s}b{b}_down", M=out, C=in_ch, R=1, S=1,
                                   H=h, W=h, stride=stride, pad=0))
            in_ch = out
            spatial = h2
    layers.append(FC("fc", 2048, 1000))
    return chain("ResNet50", layers)


def googlenet() -> DNNG:
    """GoogLeNet (Inception v1) — the 9 inception modules, standard table."""
    layers: list[LayerShape] = [
        Conv("conv1", M=64, C=3, R=7, S=7, H=224, W=224, stride=2, pad=3),
        Conv("conv2r", M=64, C=64, R=1, S=1, H=56, W=56, pad=0),
        Conv("conv2", M=192, C=64, R=3, S=3, H=56, W=56),
    ]
    # (name, H, C_in, #1x1, #3x3red, #3x3, #5x5red, #5x5, pool_proj)
    inception = [
        ("3a", 28, 192, 64, 96, 128, 16, 32, 32),
        ("3b", 28, 256, 128, 128, 192, 32, 96, 64),
        ("4a", 14, 480, 192, 96, 208, 16, 48, 64),
        ("4b", 14, 512, 160, 112, 224, 24, 64, 64),
        ("4c", 14, 512, 128, 128, 256, 24, 64, 64),
        ("4d", 14, 512, 112, 144, 288, 32, 64, 64),
        ("4e", 14, 528, 256, 160, 320, 32, 128, 128),
        ("5a", 7, 832, 256, 160, 320, 32, 128, 128),
        ("5b", 7, 832, 384, 192, 384, 48, 128, 128),
    ]
    for nm, h, cin, c1, c3r, c3, c5r, c5, pp in inception:
        layers += [
            Conv(f"i{nm}_1x1", M=c1, C=cin, R=1, S=1, H=h, W=h, pad=0),
            Conv(f"i{nm}_3x3r", M=c3r, C=cin, R=1, S=1, H=h, W=h, pad=0),
            Conv(f"i{nm}_3x3", M=c3, C=c3r, R=3, S=3, H=h, W=h),
            Conv(f"i{nm}_5x5r", M=c5r, C=cin, R=1, S=1, H=h, W=h, pad=0),
            Conv(f"i{nm}_5x5", M=c5, C=c5r, R=5, S=5, H=h, W=h, pad=2),
            Conv(f"i{nm}_pool", M=pp, C=cin, R=1, S=1, H=h, W=h, pad=0),
        ]
    layers.append(FC("fc", 1024, 1000))
    return chain("GoogleNet", layers)


def sa_cnn() -> DNNG:
    """Sentiment-analysis CNN [23]: conv windows over fastText embeddings."""
    seq, emb = 56, 300
    return chain("SA_CNN", [
        LayerShape(M=100, N=1, C=emb, R=3, S=1, H=seq, W=1, P=seq - 2, Q=1,
                   name="conv3"),
        LayerShape(M=100, N=1, C=emb, R=4, S=1, H=seq, W=1, P=seq - 3, Q=1,
                   name="conv4"),
        LayerShape(M=100, N=1, C=emb, R=5, S=1, H=seq, W=1, P=seq - 4, Q=1,
                   name="conv5"),
        FC("fc", 300, 2),
    ])


def sa_lstm() -> DNNG:
    """Regional CNN-LSTM for dimensional sentiment [24]."""
    return chain("SA_LSTM", [
        LayerShape(M=64, N=1, C=300, R=3, S=1, H=56, W=1, P=54, Q=1,
                   name="region_conv"),
        LSTM("lstm1", input_size=64, hidden=512, steps=54),
        LSTM("lstm2", input_size=512, hidden=512, steps=54),
        FC("fc", 512, 2),
    ])


def ncf() -> DNNG:
    """Neural collaborative filtering [25]: small MLP tower, batch folded."""
    batch = 256
    return chain("NCF", [
        FC("mlp1", 256, 256, batch=batch),
        FC("mlp2", 256, 128, batch=batch),
        FC("mlp3", 128, 64, batch=batch),
        FC("mlp4", 64, 32, batch=batch),
        FC("predict", 32, 1, batch=batch),
    ])


def alphagozero() -> DNNG:
    layers: list[LayerShape] = [
        Conv("stem", M=256, C=17, R=3, S=3, H=19, W=19)]
    for i in range(19):
        layers.append(Conv(f"res{i}a", M=256, C=256, R=3, S=3, H=19, W=19))
        layers.append(Conv(f"res{i}b", M=256, C=256, R=3, S=3, H=19, W=19))
    layers += [
        Conv("policy_conv", M=2, C=256, R=1, S=1, H=19, W=19, pad=0),
        FC("policy_fc", 722, 362),
        Conv("value_conv", M=1, C=256, R=1, S=1, H=19, W=19, pad=0),
        FC("value_fc1", 361, 256),
        FC("value_fc2", 256, 1),
    ]
    return chain("AlphaGoZero", layers)


def transformer() -> DNNG:
    """Transformer-base [27]: 6 enc + 6 dec, d=512, d_ff=2048, seq 128.

    Block GEMMs only — the vocab projection is excluded, consistent with
    Scale-Sim topology files which model the recurrent/attention/FF GEMMs.
    """
    d, dff, seq = 512, 2048, 128
    layers: list[LayerShape] = []
    for i in range(6):
        layers += [
            FC(f"enc{i}_qkv", d, 3 * d, batch=seq),
            FC(f"enc{i}_attn_out", d, d, batch=seq),
            FC(f"enc{i}_ff1", d, dff, batch=seq),
            FC(f"enc{i}_ff2", dff, d, batch=seq),
        ]
    for i in range(6):
        layers += [
            FC(f"dec{i}_qkv", d, 3 * d, batch=seq),
            FC(f"dec{i}_attn_out", d, d, batch=seq),
            FC(f"dec{i}_xqkv", d, 3 * d, batch=seq),
            FC(f"dec{i}_xattn_out", d, d, batch=seq),
            FC(f"dec{i}_ff1", d, dff, batch=seq),
            FC(f"dec{i}_ff2", dff, d, batch=seq),
        ]
    return chain("Transformer", layers)


# ---------------------------------------------------------------------------
# Light RNN workload
# ---------------------------------------------------------------------------

def melody_lstm() -> DNNG:
    """Melody extraction LSTM-RNN [28]: spectrogram frames -> pitch labels.

    Audio workload: 10 ms frames, one 1 s request chunk = 100 frames,
    512-unit 3-layer stack.
    """
    steps = 100
    return chain("MelodyLSTM", [
        LSTM("lstm1", input_size=513, hidden=512, steps=steps),
        LSTM("lstm2", input_size=512, hidden=512, steps=steps),
        LSTM("lstm3", input_size=512, hidden=512, steps=steps),
        FC("out", 512, 722, batch=steps),
    ])


def google_translate() -> DNNG:
    """GNMT [29]: 8 encoder + 8 decoder LSTM(1024) layers + attention.

    One 20-token sentence (typical MT inference length).  The vocab softmax
    projection is excluded, consistent with Scale-Sim topology convention.
    """
    steps = 20
    layers: list[LayerShape] = [
        LSTM("enc_bi_fwd", input_size=1024, hidden=1024, steps=steps),
        LSTM("enc_bi_bwd", input_size=1024, hidden=1024, steps=steps),
    ]
    for i in range(6):
        layers.append(LSTM(f"enc{i + 2}", input_size=1024, hidden=1024,
                           steps=steps))
    layers.append(FC("attention", 1024, 1024, batch=steps))
    for i in range(8):
        layers.append(LSTM(f"dec{i}", input_size=1024 if i else 2048,
                           hidden=1024, steps=steps))
    return chain("GoogleTranslate", layers)


def deep_voice() -> DNNG:
    """Deep Voice [30]: segmentation/duration/f0 GRUs + vocoder stack.

    The vocoder dominates: Deep Voice's synthesis RNN runs per audio sample
    (one 0.1 s request chunk at 16 kHz = 1600 steps, hidden 512).
    """
    return chain("DeepVoice", [
        LSTM("g2p_enc", input_size=256, hidden=256, steps=40),
        LSTM("g2p_dec", input_size=256, hidden=256, steps=40),
        LSTM("duration", input_size=256, hidden=256, steps=40),
        LSTM("f0_rnn", input_size=256, hidden=256, steps=80),
        LSTM("vocoder_rnn", input_size=512, hidden=512, steps=1600),
        FC("vocoder_proj", 512, 513, batch=1600),
    ])


def handwriting_lstm() -> DNNG:
    """Fast multi-language online handwriting LSTM [31]: 3xLSTM over one
    200-point pen-stroke sequence."""
    steps = 200
    return chain("HandwritingLSTM", [
        LSTM("lstm1", input_size=32, hidden=128, steps=steps),
        LSTM("lstm2", input_size=128, hidden=128, steps=steps),
        LSTM("lstm3", input_size=128, hidden=128, steps=steps),
        FC("ctc_out", 128, 100, batch=steps),
    ])


# ---------------------------------------------------------------------------

def _stagger(dnngs: list[DNNG], step_s: float) -> list[DNNG]:
    """Arrival times per Fig. 4: A_t1..A_tn land inside L0 of DNNG_0."""
    import dataclasses as _dc
    return [_dc.replace(g, arrival_time=i * step_s)
            for i, g in enumerate(dnngs)]


def heavy_workload(stagger_s: float = 2e-6) -> list[DNNG]:
    """Table 1, group 1 — multi-domain heavy load."""
    return _stagger([alexnet(), resnet50(), googlenet(), sa_cnn(), sa_lstm(),
                     ncf(), alphagozero(), transformer()], stagger_s)


def light_workload(stagger_s: float = 2e-6) -> list[DNNG]:
    """Table 1, group 2 — RNN light load."""
    return _stagger([melody_lstm(), google_translate(), deep_voice(),
                     handwriting_lstm()], stagger_s)


WORKLOADS = {
    "heavy": heavy_workload,
    "light": light_workload,
}

# Table-1 models individually, for per-job sampling by the open-loop traffic
# generator (`repro.traffic.arrivals`): each arrival picks ONE model from a
# pool instead of replaying the whole closed workload at t≈0; then the models
# beyond Table 1 (`repro.sim.mellum`), in no pool.
MODELS = {
    "AlexNet": alexnet,
    "ResNet50": resnet50,
    "GoogleNet": googlenet,
    "SA_CNN": sa_cnn,
    "SA_LSTM": sa_lstm,
    "NCF": ncf,
    "AlphaGoZero": alphagozero,
    "Transformer": transformer,
    "MelodyLSTM": melody_lstm,
    "GoogleTranslate": google_translate,
    "DeepVoice": deep_voice,
    "HandwritingLSTM": handwriting_lstm,
    mellum.NAME: mellum.mellum2_12b_a2_5b,
}

_HEAVY = ("AlexNet", "ResNet50", "GoogleNet", "SA_CNN", "SA_LSTM", "NCF",
          "AlphaGoZero", "Transformer")
_LIGHT = ("MelodyLSTM", "GoogleTranslate", "DeepVoice", "HandwritingLSTM")
MODEL_POOLS = {
    "heavy": _HEAVY,
    "light": _LIGHT,
    "all": _HEAVY + _LIGHT,
}


_TEMPLATES: dict = {}


def _model_template(model: str) -> DNNG:
    """Memoized Table-1 template: model constructors are pure, and the
    open-loop generator stamps thousands of per-job clones — rebuilding
    every LayerShape per arrival was measurable on the serving hot path.
    Cloning via ``dataclasses.replace`` shares the (frozen) layer tuple, so
    all jobs of one model also share the scheduler's cost-oracle cache
    entries.  Keyed by the constructor object itself, so a patched
    ``MODELS`` registry (ablations, tests) misses the cache as it should."""
    fn = MODELS[model]
    g = _TEMPLATES.get(fn)
    if g is None:
        g = _TEMPLATES[fn] = fn()
    return g


def sample_dnng(rng, pool: str = "all", name: str | None = None,
                arrival_time: float = 0.0) -> DNNG:
    """One fresh Table-1 DNNG for an arriving job.

    ``rng`` is a seeded ``random.Random`` (determinism lives with the
    caller); ``pool`` selects the sampling universe (``MODEL_POOLS``);
    ``name`` overrides the tenant name so concurrent jobs of the same model
    stay distinct in the scheduler.
    """
    if pool not in MODEL_POOLS:
        raise ValueError(f"unknown pool {pool!r}; known: "
                         f"{sorted(MODEL_POOLS)}")
    model = rng.choice(MODEL_POOLS[pool])
    return _model_template(model).clone(name=name, arrival_time=arrival_time)

"""Published memory math for BASELINE config 4 (ERNIE-1.5B on v5e).

Answers: can full-depth ernie_1p5b (1.637B params)
train on ONE v5e (16 GiB HBM) under the bench's regime (bf16 compute,
f32 Adam masters, per-block remat)? Run:  python tools/memory_math.py

Accounting per trainable param count N (the engine's actual residency):
  * f32 master params            4 N   (ParallelEngine inputs)
  * f32 Adam moments (m, v)      8 N   (optimizer slots)
  * f32 grads                    4 N   (transient; param-layout pinned)
  * bf16 compute param copy      2 N   (amp cast inside the step)
  * activations under remat      ~L*2*B*S*H bf16 boundaries + one
                                 block's recompute peak

Conclusion (printed): 24 layers needs ~28 GiB => does NOT fit a single
v5e; the largest depth that fits with margin is 10 layers (~13 GiB).
Config 4's single-chip number is therefore an L=10 depth-proxy with the
per-layer compute identical to full scale (same H/I/heads); full depth
runs sharded (ZeRO-2 over >= 4 chips — engine path validated on the
virtual 8-device mesh by dryrun_multichip / test_sharding_remat).
"""

GiB = 1024 ** 3


def ernie_params(layers, H=2304, I=9216, V=40000, P=2048):
    lp = (4 * H * H + 4 * H) + (H * I + I + I * H + H) + 4 * H
    emb = V * H + P * H + 2 * H + 2 * H
    pooler = H * H + H
    head = H * H + H + V + 2 * H  # decoder ties the word embedding
    nsp = H * 2 + 2
    return emb + layers * lp + pooler + head + nsp


def budget(layers, batch=4, seq=512, H=2304, I=9216):
    n = ernie_params(layers, H=H, I=I)
    static = 12 * n                      # master + adam moments, f32
    grads = 4 * n
    bf16 = 2 * n
    act = layers * batch * seq * H * 2 * 2 + batch * seq * I * 2 * 6
    return n, static, grads, bf16, act, static + grads + bf16 + act


def main():
    print(f"{'L':>3} {'params':>8} {'static':>8} {'grads':>7} "
          f"{'bf16':>6} {'acts':>6} {'peak':>8}  fits 16GiB v5e?")
    for layers in (24, 12, 10, 8, 6):
        n, st, g, b, a, tot = budget(layers)
        fits = "YES" if tot < 15 * GiB else "no"
        print(f"{layers:>3} {n / 1e9:>7.2f}B {st / GiB:>7.1f}G "
              f"{g / GiB:>6.1f}G {b / GiB:>5.1f}G {a / GiB:>5.2f}G "
              f"{tot / GiB:>7.1f}G  {fits}")
    n24 = ernie_params(24)
    for chips in (2, 4, 8):
        # ZeRO-2: moments+grads shard over chips; master params + bf16
        # copy stay replicated (stage 2)
        per = (4 * n24 + 2 * n24) + (8 * n24 + 4 * n24) / chips + \
            budget(24)[4]
        print(f"ZeRO-2 over {chips} chips: ~{per / GiB:.1f} GiB/chip"
              + ("  <- fits" if per < 15 * GiB else ""))


if __name__ == "__main__":
    main()

// numpy's legacy shuffle of a contiguous int32 vector, bit for bit:
//
//     np.random.RandomState(seed).shuffle(x)
//
// is, for a one-dimensional array, Fisher-Yates from the top — for i = n-1
// .. 1: j = random_interval(i) (masked rejection over 32-bit MT19937
// draws), swap x[i], x[j] — in a generic loop of three memcpy calls an
// element whose every x[j] misses the cache. The draws do not depend on the
// data, so this loop takes them a batch ahead, prefetches the batch's x[j]
// and then swaps in the same order: the same permutation and the same
// generator state afterwards, several times sooner. The generator's state
// (key[624], pos) is the caller's, read from and written back to
// RandomState.get_state() / set_state().
#include <cstdint>

namespace {

constexpr int N = 624, M = 397;

inline void mt_gen(uint32_t* key) {
  uint32_t y;
  int kk;
  for (kk = 0; kk < N - M; kk++) {
    y = (key[kk] & 0x80000000U) | (key[kk + 1] & 0x7fffffffU);
    key[kk] = key[kk + M] ^ (y >> 1) ^ (-(int32_t)(y & 1) & 0x9908b0dfU);
  }
  for (; kk < N - 1; kk++) {
    y = (key[kk] & 0x80000000U) | (key[kk + 1] & 0x7fffffffU);
    key[kk] = key[kk + (M - N)] ^ (y >> 1) ^ (-(int32_t)(y & 1) & 0x9908b0dfU);
  }
  y = (key[N - 1] & 0x80000000U) | (key[0] & 0x7fffffffU);
  key[N - 1] = key[M - 1] ^ (y >> 1) ^ (-(int32_t)(y & 1) & 0x9908b0dfU);
}

inline uint32_t mt_next(uint32_t* key, int32_t* pos) {
  if (*pos >= N) {
    mt_gen(key);
    *pos = 0;
  }
  uint32_t y = key[(*pos)++];
  y ^= (y >> 11);
  y ^= (y << 7) & 0x9d2c5680U;
  y ^= (y << 15) & 0xefc60000U;
  y ^= (y >> 18);
  return y;
}

inline uint32_t interval(uint32_t max, uint32_t* key, int32_t* pos) {
  uint32_t mask = max, value;
  mask |= mask >> 1;
  mask |= mask >> 2;
  mask |= mask >> 4;
  mask |= mask >> 8;
  mask |= mask >> 16;
  while ((value = (mt_next(key, pos) & mask)) > max) {
  }
  return value;
}

}  // namespace

extern "C" void legacy_shuffle_i32(int32_t* x, int64_t n, uint32_t* key,
                                   int32_t* pos) {
  constexpr int B = 64;
  uint32_t js[B];
  int64_t i = n - 1;
  while (i >= 1) {
    int b = i >= B ? B : (int)i;
    for (int k = 0; k < b; k++) {
      js[k] = interval((uint32_t)(i - k), key, pos);
      __builtin_prefetch(x + js[k], 1);
    }
    for (int k = 0; k < b; k++) {
      int32_t t = x[js[k]];
      x[js[k]] = x[i - k];
      x[i - k] = t;
    }
    i -= b;
  }
}

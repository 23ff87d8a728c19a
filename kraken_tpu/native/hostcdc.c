/* ---------------------------------------------------------------------
 * FastCDC sequential chunker (host plane).
 *
 * Exactly kraken_tpu/ops/cdc.py chunk_reference: 32-bit gear rolling
 * hash h = (h << 1) + gear(b), FastCDC normalized cut policy (strict
 * mask through avg_size, loose mask through max_size, hard min/max
 * bounds). The TPU vector pass is the device plane; THIS is the host
 * plane for streaming workloads where the bytes never visit the chip
 * (e.g. origin-side dedup scans) -- ~1.5 GB/s/core vs ~0.2 GB/s for the
 * NumPy fallback. The gear function is the framework constant defined
 * arithmetically in ops/cdc.py; boundaries are a persistent on-disk
 * contract, so the two implementations must never diverge (pinned
 * against chunk_reference in tests/test_native.py).
 * ------------------------------------------------------------------ */

#include <stddef.h>
#include <stdint.h>

static uint32_t kt_gear_fn(uint32_t b)
{
    uint32_t x = (b + 1u) * 0x9E3779B1u;
    x ^= x >> 15;
    x *= 0x85EBCA77u;
    x ^= x >> 13;
    return x;
}

/* Chunk data[0..n) into cut end-offsets (exclusive). Returns the number
 * of cuts written (<= cuts_cap; callers size cuts_cap >= n/min_size + 1
 * so truncation cannot happen). */
size_t kt_cdc_chunk(const uint8_t *restrict data, size_t n,
                    size_t min_size, size_t avg_size, size_t max_size,
                    uint32_t mask_strict, uint32_t mask_loose,
                    uint64_t *restrict cuts_out, size_t cuts_cap)
{
    uint32_t gear[256];
    for (uint32_t i = 0; i < 256; i++)
        gear[i] = kt_gear_fn(i);
    size_t ncuts = 0;
    size_t start = 0;
    while (start < n && ncuts < cuts_cap) {
        const size_t remaining = n - start;
        if (remaining <= min_size) {
            cuts_out[ncuts++] = n;
            break;
        }
        const size_t limit = remaining < max_size ? remaining : max_size;
        const size_t norm_point = avg_size < limit ? avg_size : limit;
        const uint8_t *p = data + start;
        uint32_t h = 0;
        size_t end = start + limit;
        size_t i = 0;
        for (; i < min_size; i++) /* uncuttable zone: hash only */
            h = (h << 1) + gear[p[i]];
        for (; i < norm_point; i++) {
            h = (h << 1) + gear[p[i]];
            if ((h & mask_strict) == 0) {
                end = start + i + 1;
                goto cut;
            }
        }
        for (; i < limit; i++) {
            h = (h << 1) + gear[p[i]];
            if ((h & mask_loose) == 0) {
                end = start + i + 1;
                goto cut;
            }
        }
    cut:
        cuts_out[ncuts++] = end;
        start = end;
    }
    return ncuts;
}

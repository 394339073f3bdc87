// RowBinary walker for the benchmark's ClickHouse stand-in.
//
// The stand-in has to count, and for the comparison decode, every row the
// system inserts; a pass of the wide table is 6.9M rows x 73 columns, which
// no Python loop walks inside a run.  This file knows nothing of the program
// under test: it reads ClickHouse's documented RowBinary layout (fixed-width
// little-endian values, LEB128 length + bytes for String, one flag byte
// before a Nullable value).
//
// widths[c]: 1/2/4/8 for a fixed-width column, 0 for String.
// nullable[c]: 1 when the column is Nullable(...).

#include <cstdint>
#include <cstring>

extern "C" {

// Pass 1: rows in the payload and, per String column, the bytes its values
// take.  Returns the row count, or -1 when the payload is malformed
// (truncated value, over-long varint, trailing bytes).
long long rb_scan(const uint8_t* data, long long n, const int32_t* widths,
                  const uint8_t* nullable, int ncols,
                  long long* str_bytes /* [ncols], zeroed by caller */) {
    long long pos = 0, rows = 0;
    while (pos < n) {
        for (int c = 0; c < ncols; ++c) {
            if (nullable[c]) {
                if (pos >= n) return -1;
                uint8_t flag = data[pos++];
                if (flag == 1) continue;
                if (flag != 0) return -1;
            }
            int w = widths[c];
            if (w > 0) {
                pos += w;
            } else {
                unsigned long long len = 0;
                int shift = 0;
                for (;;) {
                    if (pos >= n || shift > 56) return -1;
                    uint8_t b = data[pos++];
                    len |= (unsigned long long)(b & 0x7F) << shift;
                    if (!(b & 0x80)) break;
                    shift += 7;
                }
                pos += (long long)len;
                str_bytes[c] += (long long)len;
            }
            if (pos > n) return -1;
        }
        ++rows;
    }
    return rows;
}

// Pass 2: columnar decode into caller-allocated buffers.
// fixed[c]:   rows * widths[c] bytes (fixed-width columns, else null)
// nulls[c]:   rows bytes, 1 = NULL (nullable columns, else null)
// offsets[c]: rows + 1 int64 (String columns, else null)
// chars[c]:   str_bytes[c] bytes (String columns, else null)
// A NULL fixed value decodes as zero bytes, a NULL string as empty.
long long rb_decode(const uint8_t* data, long long n, const int32_t* widths,
                    const uint8_t* nullable, int ncols, long long rows,
                    uint8_t** fixed, uint8_t** nulls, int64_t** offsets,
                    uint8_t** chars) {
    long long pos = 0;
    for (int c = 0; c < ncols; ++c)
        if (widths[c] == 0) offsets[c][0] = 0;
    for (long long r = 0; r < rows; ++r) {
        for (int c = 0; c < ncols; ++c) {
            int w = widths[c];
            bool is_null = false;
            if (nullable[c]) {
                is_null = data[pos++] == 1;
                nulls[c][r] = is_null ? 1 : 0;
            }
            if (w > 0) {
                if (is_null) {
                    memset(fixed[c] + r * w, 0, w);
                } else {
                    memcpy(fixed[c] + r * w, data + pos, w);
                    pos += w;
                }
            } else {
                int64_t at = offsets[c][r];
                if (!is_null) {
                    unsigned long long len = 0;
                    int shift = 0;
                    for (;;) {
                        uint8_t b = data[pos++];
                        len |= (unsigned long long)(b & 0x7F) << shift;
                        if (!(b & 0x80)) break;
                        shift += 7;
                    }
                    memcpy(chars[c] + at, data + pos, len);
                    pos += (long long)len;
                    at += (int64_t)len;
                }
                offsets[c][r + 1] = at;
            }
        }
    }
    return pos == n ? rows : -1;
}

}  // extern "C"

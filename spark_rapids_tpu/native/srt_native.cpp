// Native host-side control-plane kernels.
//
// Reference parity: the reference's host control plane is C++/JVM-native
// (parquet-mr page walking + cudf's C++ RLE machinery feeding the GPU
// decoder, GpuParquetScan.scala:316-458; cudf's C++ CSV tokenizer feeding
// the device parser, GpuBatchScanExec.scala:322-520). Here the TPU
// framework keeps the same split: the device data plane is XLA, and these
// byte-level host loops — RLE/bit-packed run-table extraction, thrift
// page-header walking, a chunk's SNAPPY pages, and CSV field-boundary
// scanning — run natively instead of interpreting bytes in Python.
//
// Built as a plain shared object; Python binds via ctypes
// (spark_rapids_tpu/native/__init__.py) and falls back to the pure-Python
// implementations when the .so is absent.

#include <cstdint>
#include <cstring>

// ---------------------------------------------------------------------------
// Thrift compact-protocol reader (just enough for parquet PageHeader).
// ---------------------------------------------------------------------------
namespace {

struct Reader {
    const uint8_t* buf;
    int64_t pos;
    int64_t end;
    bool err = false;

    uint64_t varint() {
        uint64_t out = 0;
        int shift = 0;
        for (;;) {
            if (pos >= end || shift > 63) { err = true; return 0; }
            uint8_t b = buf[pos++];
            out |= (uint64_t)(b & 0x7F) << shift;
            if (!(b & 0x80)) return out;
            shift += 7;
        }
    }

    int64_t zigzag() {
        uint64_t v = varint();
        return (int64_t)(v >> 1) ^ -(int64_t)(v & 1);
    }

    void skip_value(int ftype);

    // Parse a struct, reporting (fid, ftype) to `cb`; the callback returns
    // true when it consumed the value itself (possibly recursing).
    template <typename F>
    void parse_struct(F&& cb) {
        int64_t fid = 0;
        for (;;) {
            if (pos >= end) { err = true; return; }
            uint8_t b = buf[pos++];
            if (b == 0) return;
            int delta = b >> 4;
            int ftype = b & 0x0F;
            fid = delta ? fid + delta : zigzag();
            if (err) return;
            if (!cb(fid, ftype, *this)) skip_value(ftype);
            if (err) return;
        }
    }
};

void Reader::skip_value(int ftype) {
    // every length below is validated against the remaining bytes BEFORE
    // advancing — corrupt varints must never move `pos` backward or spin
    // (the python fallback throws on the same inputs; native must too)
    switch (ftype) {
        case 1: case 2: return;            // bool encoded in the type
        case 3: ++pos; return;             // i8
        case 4: case 5: case 6: zigzag(); return;
        case 7: pos += 8; return;          // double
        case 8: {                          // binary/string
            uint64_t n = varint();
            if (err || n > (uint64_t)(end - pos)) { err = true; return; }
            pos += (int64_t)n;
            return;
        }
        case 9: case 10: {                 // list/set
            if (pos >= end) { err = true; return; }
            uint8_t b = buf[pos++];
            uint64_t n = b >> 4;
            int et = b & 0x0F;
            if (n == 15) n = varint();
            if (err) return;
            if (et == 1 || et == 2) return;  // bools consume no bytes
            // each remaining element consumes >= 1 byte; a count beyond
            // the buffer is malformed, not a long loop
            if (n > (uint64_t)(end - pos)) { err = true; return; }
            for (uint64_t i = 0; i < n && !err; ++i) skip_value(et);
            return;
        }
        case 12:                           // struct
            parse_struct([](int64_t, int, Reader&) { return false; });
            return;
        default:
            err = true;
    }
}

}  // namespace

extern "C" {

// Parse one parquet RLE/bit-packed hybrid stream into a run table.
// Returns the number of runs written, or -1 if max_runs was too small,
// -2 on a malformed varint.
//
//   buf[start:end) : the raw chunk bytes containing the hybrid stream
//   bit_width      : value bit width (dict index width or 1 for def levels)
//   num_values     : logical values to account for
//   out_start[i]   : output index where run i begins
//   is_rle[i]      : 1 = RLE run (value[i] repeated), 0 = bit-packed
//   value[i]       : the repeated value for RLE runs
//   bit_off[i]     : absolute BIT offset of packed values for bp runs
int64_t srt_parse_runs(const uint8_t* buf, int64_t start, int64_t end,
                       int32_t bit_width, int64_t num_values,
                       int64_t* out_start, uint8_t* is_rle, int32_t* value,
                       int64_t* bit_off, int64_t max_runs,
                       int64_t* produced_out) {
    int64_t pos = start;
    int64_t produced = 0;
    int64_t n = 0;
    const int32_t vbytes = (bit_width + 7) / 8;
    while (produced < num_values && pos < end) {
        // LEB128 varint header
        uint64_t header = 0;
        int shift = 0;
        for (;;) {
            if (pos >= end || shift > 63) return -2;
            uint8_t b = buf[pos++];
            header |= (uint64_t)(b & 0x7F) << shift;
            if (!(b & 0x80)) break;
            shift += 7;
        }
        if (n >= max_runs) return -1;
        if (header & 1) {  // bit-packed: (header>>1) groups of 8 values
            int64_t groups = (int64_t)(header >> 1);
            // a group count whose bytes run past the stream is malformed —
            // reject before the multiply can overflow or move pos wild
            if (groups < 0 || (bit_width > 0 &&
                               groups > (end - pos) / bit_width + 1))
                return -2;
            out_start[n] = produced;
            is_rle[n] = 0;
            value[n] = 0;
            bit_off[n] = pos * 8;
            pos += groups * bit_width;
            produced += groups * 8;
        } else {           // RLE: (header>>1) copies of one LE value
            int64_t count = (int64_t)(header >> 1);
            // accumulate unsigned: shifting into the sign bit of a signed
            // int is UB; a single cast at the end is well-defined
            uint32_t uv = 0;
            for (int32_t k = 0; k < vbytes && pos + k < end; ++k)
                uv |= (uint32_t)buf[pos + k] << (8 * k);
            int32_t v = (int32_t)uv;
            pos += vbytes;
            out_start[n] = produced;
            is_rle[n] = 1;
            value[n] = v;
            bit_off[n] = 0;
            produced += count;
        }
        ++n;
    }
    *produced_out = produced;
    return n;
}

// Walk the page headers of one raw column chunk (python fallback:
// io/parquet_device.py parse_pages). Returns the page count or
//   -1 : max_pages too small      -2 : malformed thrift
//   -4 : unsupported page type (v2 etc.) — caller falls back to Arrow
int64_t srt_parse_pages(const uint8_t* buf, int64_t len,
                        int32_t* kind, int64_t* num_values,
                        int32_t* encoding, int64_t* data_start,
                        int64_t* data_len, int64_t max_pages) {
    int64_t n = 0;
    int64_t pos = 0;
    while (pos < len) {
        Reader r{buf, pos, len};
        int64_t ph_type = -1, ph_comp = -1;
        int64_t dp_num = -1, dp_enc = -1, di_num = -1;
        r.parse_struct([&](int64_t fid, int ftype, Reader& rr) {
            if (fid == 1 && ftype >= 4 && ftype <= 6) {        // page type
                ph_type = rr.zigzag();
                return true;
            }
            if (fid == 3 && ftype >= 4 && ftype <= 6) {        // comp. size
                ph_comp = rr.zigzag();
                return true;
            }
            if (fid == 5 && ftype == 12) {                     // data v1 hdr
                rr.parse_struct([&](int64_t f2, int t2, Reader& r2) {
                    if (f2 == 1 && t2 >= 4 && t2 <= 6) {
                        dp_num = r2.zigzag();
                        return true;
                    }
                    if (f2 == 2 && t2 >= 4 && t2 <= 6) {
                        dp_enc = r2.zigzag();
                        return true;
                    }
                    return false;
                });
                return true;
            }
            if (fid == 7 && ftype == 12) {                     // dict hdr
                rr.parse_struct([&](int64_t f2, int t2, Reader& r2) {
                    if (f2 == 1 && t2 >= 4 && t2 <= 6) {
                        di_num = r2.zigzag();
                        return true;
                    }
                    return false;
                });
                return true;
            }
            return false;
        });
        if (r.err || ph_comp < 0 || ph_type < 0) return -2;
        if (ph_comp > len - r.pos) return -2;  // payload past the buffer
        if (n >= max_pages) return -1;
        if (ph_type == 2) {            // dictionary page
            kind[n] = 2;
            num_values[n] = di_num;
            encoding[n] = 0;           // dict payload reads as PLAIN
        } else if (ph_type == 0) {     // data page v1
            if (dp_num < 0 || dp_enc < 0) return -2;
            kind[n] = 0;
            num_values[n] = dp_num;
            encoding[n] = (int32_t)dp_enc;
        } else {
            return -4;
        }
        data_start[n] = r.pos;
        data_len[n] = ph_comp;
        ++n;
        pos = r.pos + ph_comp;
    }
    return n;
}

// Single-pass CSV field-boundary scan (the host control plane of the
// device CSV parser, io/csv_device.py). Replaces a multi-pass numpy scan
// with one cache-friendly sweep that simultaneously finds boundaries,
// validates column counts per line, rejects quoted fields, and trims CRLF.
//
// Returns the number of data rows written, or
//   -1 : structure not eligible (quote char seen, ragged line)
//   -3 : more rows than max_rows (caller re-allocates and retries)
//
//   starts/lens : int32 [max_rows * ncols], row-major
int64_t srt_csv_plan(const uint8_t* buf, int64_t len, uint8_t sep,
                     int32_t ncols, int32_t* starts, int32_t* lens,
                     int64_t max_rows) {
    if (len <= 0) return -1;
    int64_t row = 0;
    int32_t col = 0;
    int64_t field_start = 0;
    for (int64_t i = 0; i <= len; ++i) {
        const bool at_eof = (i == len);
        const uint8_t c = at_eof ? (uint8_t)'\n' : buf[i];
        if (c == (uint8_t)'"') return -1;
        if (c == sep || c == (uint8_t)'\n') {
            // EOF acts as a virtual newline only for a non-empty last line
            if (at_eof && col == 0 && field_start == i) break;
            if (c == sep) {
                if (col >= ncols - 1) return -1;  // too many fields
            } else {
                if (col != ncols - 1) return -1;  // too few fields
            }
            if (row >= max_rows) return -3;
            int32_t flen = (int32_t)(i - field_start);
            // trim a trailing \r before a newline (CRLF files)
            if (c == (uint8_t)'\n' && flen > 0 &&
                buf[i - 1] == (uint8_t)'\r')
                --flen;
            starts[row * ncols + col] = (int32_t)field_start;
            lens[row * ncols + col] = flen;
            field_start = i + 1;
            if (c == sep) {
                ++col;
            } else {
                col = 0;
                ++row;
            }
        }
    }
    if (col != 0) return -1;  // dangling partial line (shouldn't happen)
    return row;
}

// Walk a parquet PLAIN byte-array page: n values of (u32 LE length +
// bytes). Fills absolute starts/lens; returns n or -1 on truncation.
int64_t srt_plain_strings(const uint8_t* buf, int64_t pos, int64_t end,
                          int64_t n, int32_t* starts, int32_t* lens) {
  for (int64_t i = 0; i < n; i++) {
    if (pos + 4 > end) return -1;
    uint32_t ln = (uint32_t)buf[pos] | ((uint32_t)buf[pos + 1] << 8) |
                  ((uint32_t)buf[pos + 2] << 16) |
                  ((uint32_t)buf[pos + 3] << 24);
    pos += 4;
    if ((int64_t)ln > end - pos) return -1;
    starts[i] = (int32_t)pos;
    lens[i] = (int32_t)ln;
    pos += (int64_t)ln;
  }
  return n;
}

// One snappy block (the raw format parquet's SNAPPY codec stores a page
// in: a varint uncompressed length, then literal and copy elements)
// expanded into dst[0, dst_len). Returns 0, or a negative code where the
// block is malformed or does not fill dst exactly.
static int snappy_block(const uint8_t* src, int64_t n, uint8_t* dst,
                        int64_t dst_len) {
    int64_t pos = 0;
    uint64_t ulen = 0;
    for (int shift = 0;; shift += 7) {
        if (pos >= n || shift > 35) return -1;
        const uint8_t b = src[pos++];
        ulen |= (uint64_t)(b & 0x7F) << shift;
        if (!(b & 0x80)) break;
    }
    if ((int64_t)ulen != dst_len) return -2;
    int64_t out = 0;
    while (pos < n) {
        const uint8_t tag = src[pos++];
        int64_t len, off;
        switch (tag & 3) {
        case 0: {  // literal: length - 1 in the tag, or in 1-4 bytes after
            len = tag >> 2;
            if (len >= 60) {
                const int nb = (int)len - 59;
                if (pos + nb > n) return -1;
                len = 0;
                for (int i = 0; i < nb; ++i)
                    len |= (int64_t)src[pos + i] << (8 * i);
                pos += nb;
            }
            len += 1;
            if (len > n - pos || len > dst_len - out) return -1;
            if (len <= 16 && n - pos >= 16 && dst_len - out >= 16)
                std::memcpy(dst + out, src + pos, 16);  // fixed size: inlined
            else
                std::memcpy(dst + out, src + pos, (size_t)len);
            pos += len;
            out += len;
            continue;
        }
        case 1:  // copy, 1-byte offset: 3 bits of length, 11 of offset
            if (pos + 1 > n) return -1;
            len = 4 + ((tag >> 2) & 7);
            off = ((int64_t)(tag >> 5) << 8) | src[pos];
            pos += 1;
            break;
        case 2:  // copy, 2-byte offset
            if (pos + 2 > n) return -1;
            len = 1 + (tag >> 2);
            off = (int64_t)src[pos] | ((int64_t)src[pos + 1] << 8);
            pos += 2;
            break;
        default:  // copy, 4-byte offset
            if (pos + 4 > n) return -1;
            len = 1 + (tag >> 2);
            off = (int64_t)src[pos] | ((int64_t)src[pos + 1] << 8) |
                  ((int64_t)src[pos + 2] << 16) |
                  ((int64_t)src[pos + 3] << 24);
            pos += 4;
            break;
        }
        if (off <= 0 || off > out || len > dst_len - out) return -1;
        if (len <= 16 && off >= 8 && dst_len - out >= 16) {
            // two fixed 8-byte moves (inlined); writing past len is fine,
            // the next element overwrites it
            std::memcpy(dst + out, dst + out - off, 8);
            std::memcpy(dst + out + 8, dst + out - off + 8, 8);
        } else if (off >= len) {
            std::memcpy(dst + out, dst + out - off, (size_t)len);
        } else {  // the copy overlaps what it writes: a run, byte by byte
            for (int64_t i = 0; i < len; ++i)
                dst[out + i] = dst[out + i - off];
        }
        out += len;
    }
    return out == dst_len ? 0 : -3;
}

// Every SNAPPY page payload of one column chunk decompressed in ONE call
// (io/parquet_device.py normalize_chunk): page i's payload
// chunk[src_off[i], +src_len[i]) into out[dst_off[i], +dst_len[i]). One
// call a chunk, not one a page, because the caller runs beside other
// Python threads and every call out of the interpreter is a hand-over of
// its lock. Returns 0, or -(i + 1) for the first page that does not
// decompress to its header's size.
int64_t srt_snappy_pages(const uint8_t* chunk, int64_t chunk_len,
                         int64_t n_pages, const int64_t* src_off,
                         const int64_t* src_len, const int64_t* dst_off,
                         const int64_t* dst_len, uint8_t* out,
                         int64_t out_len) {
    for (int64_t i = 0; i < n_pages; ++i) {
        if (src_off[i] < 0 || src_len[i] < 0 ||
            src_off[i] > chunk_len - src_len[i] || dst_off[i] < 0 ||
            dst_len[i] < 0 || dst_off[i] > out_len - dst_len[i])
            return -(i + 1);
        if (src_len[i] == 0 && dst_len[i] == 0) continue;
        if (snappy_block(chunk + src_off[i], src_len[i], out + dst_off[i],
                         dst_len[i]) != 0)
            return -(i + 1);
    }
    return 0;
}

}  // extern "C"

// directio — whole-file O_DIRECT reads of many files in one call.
//
// What utils/direct_io.py `_read_direct_raw` + `read_file_direct` do for
// one file, done for a vector of paths with no interpreter in between:
// the scrub's I/O lane (block/repair.py `_read_slice`) reads a slice of
// ~64 block files, and every `os.open`, `os.fstat`, `os.preadv` and
// `os.close` of the per-file code is a return to the interpreter, so a
// chance to lose its lock to another thread, and the copy out of the
// aligned buffer is made WITH the lock held.  ctypes drops the lock for
// the whole of a call into this library, so a slice costs two hand-overs
// instead of six a file:
//
//   dio_open   open(O_RDONLY|O_DIRECT) + fstat of every path (sizes are
//              not known before); the fds stay open
//   (Python allocates one uninitialised `bytes` a file, of its size)
//   dio_read   preadv in chunks into this thread's page-aligned scratch
//              buffer (kept warm: fresh pages cost ~40% of an O_DIRECT
//              read), the copy out into the `bytes`, close
//   dio_close  closes what dio_open left open, for a caller that cannot
//              go on to dio_read
//
// The fallback contract is direct_io.py's: an open that refuses O_DIRECT
// is made again without it and the file read straight into its
// destination (mode buffered, no copy); a preadv that fails mid-file
// hands the remainder to a plain fd (mode buffered).  Every fd is closed
// on every path.  Stage times are CLOCK_MONOTONIC nanoseconds, the clock
// `time.monotonic_ns` reads.
//
// Exposed as a C ABI consumed by ops/native.py over ctypes.

#include <algorithm>
#include <cerrno>
#include <cstdint>
#include <cstring>

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <time.h>
#include <unistd.h>

namespace {

constexpr int64_t PAGE = 4096;
constexpr int32_t MODE_DIRECT = 0;
constexpr int32_t MODE_BUFFERED = 1;

int64_t now_ns() {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return int64_t(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

int64_t page_cap(int64_t size) {
    return std::max((size + PAGE - 1) & ~(PAGE - 1), PAGE);
}

// The calling thread's destination of O_DIRECT reads: anonymous pages
// (aligned for any sector size), grown geometrically and reused, gone
// with the thread.
struct Scratch {
    char* p = nullptr;
    int64_t cap = 0;
    ~Scratch() { if (p) munmap(p, size_t(cap)); }
    char* ensure(int64_t want) {
        if (want <= cap) return p;
        int64_t grow = std::max(want, 2 * cap);
        void* q = mmap(nullptr, size_t(grow), PROT_READ | PROT_WRITE,
                       MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
        if (q == MAP_FAILED) return nullptr;
        if (p) munmap(p, size_t(cap));
        p = static_cast<char*>(q);
        cap = grow;
        return p;
    }
};
thread_local Scratch scratch;

int open_retry(const char* path, int flags) {
    int fd;
    do fd = open(path, flags); while (fd < 0 && errno == EINTR);
    return fd;
}

}  // namespace

extern "C" {

struct DioFile {
    int64_t size;      // fstat's, set by dio_open
    int64_t got;       // bytes in the destination, set by dio_read
    int64_t open_ns;   // open + fstat (+ the scratch buffer growing)
    int64_t pread_ns;  // the read loop, a fallback's included
    int64_t copy_ns;   // scratch -> destination
    int64_t rest_ns;   // close
    int32_t fd;        // open between the two calls, else -1
    int32_t err;       // errno of what failed, 0 for a read that came back
    int32_t mode;      // MODE_DIRECT | MODE_BUFFERED
    int32_t pad_;
};

// Open and fstat every path.  `o_direct` is the flag to ask for (0 on a
// platform without one: every read is then buffered).
void dio_open(const char* const* paths, int64_t n, int32_t o_direct,
              DioFile* files) {
    int64_t largest = 0, at = -1;
    for (int64_t i = 0; i < n; i++) {
        DioFile& f = files[i];
        f = DioFile{};
        int64_t t0 = now_ns();
        f.mode = o_direct ? MODE_DIRECT : MODE_BUFFERED;
        f.fd = open_retry(paths[i], O_RDONLY | O_CLOEXEC | o_direct);
        if (f.fd < 0 && o_direct && errno != ENOENT) {
            f.mode = MODE_BUFFERED;
            f.fd = open_retry(paths[i], O_RDONLY | O_CLOEXEC);
        }
        if (f.fd < 0) {
            f.err = errno;
        } else {
            struct stat st;
            if (fstat(f.fd, &st) != 0) {
                f.err = errno;
                close(f.fd);
                f.fd = -1;
            } else {
                f.size = st.st_size;
                if (f.mode == MODE_DIRECT && f.size > largest) {
                    largest = f.size;
                    at = i;
                }
            }
        }
        f.open_ns = now_ns() - t0;
    }
    if (at >= 0) {      // where `open` has always counted the buffer's growth
        int64_t t0 = now_ns();
        scratch.ensure(page_cap(largest));
        files[at].open_ns += now_ns() - t0;
    }
}

void dio_close(int64_t n, DioFile* files) {
    for (int64_t i = 0; i < n; i++) {
        if (files[i].fd >= 0) close(files[i].fd);
        files[i].fd = -1;
    }
}

// Read every file dio_open opened into `dest[i]` (room for files[i].size
// bytes), `chunk` bytes a request (a multiple of the page), and close it.
void dio_read(const char* const* paths, int64_t n, int64_t chunk,
              char* const* dest, DioFile* files) {
    for (int64_t i = 0; i < n; i++) {
        DioFile& f = files[i];
        if (f.fd < 0) continue;
        int64_t t0 = now_ns();
        int64_t size = f.size, cap = page_cap(size), off = 0;
        // a plain fd asks for no alignment: straight into the destination
        char* buf = dest[i];
        if (f.mode == MODE_DIRECT) {
            buf = scratch.ensure(cap);
            if (!buf) f.err = ENOMEM;
        } else {
            cap = size;
        }
        while (!f.err && off < size) {
            ssize_t r = pread(f.fd, buf + off,
                              size_t(std::min(chunk, cap - off)), off);
            if (r > 0) {
                off += r;
            } else if (r == 0) {
                break;
            } else if (errno == EINTR) {
                continue;
            } else if (f.mode == MODE_DIRECT) {
                // mid-file refusal: the remainder through a plain fd
                f.mode = MODE_BUFFERED;
                int fd = open_retry(paths[i], O_RDONLY | O_CLOEXEC);
                if (fd < 0) {
                    f.err = errno;
                } else {
                    close(f.fd);
                    f.fd = fd;
                }
            } else {
                f.err = errno;
            }
        }
        int64_t t1 = now_ns(), t2 = t1;
        f.pread_ns = t1 - t0;
        if (!f.err) {
            f.got = off;
            if (buf != dest[i]) {   // else nothing was copied: copy_ns 0
                memcpy(dest[i], buf, size_t(off));
                t2 = now_ns();
            }
        }
        f.copy_ns = t2 - t1;
        close(f.fd);
        f.fd = -1;
        f.rest_ns = now_ns() - t2;
    }
}

}  // extern "C"
